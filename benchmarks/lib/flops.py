"""Work of the algorithm: FLOPs and least bytes, from the reference's jaxpr.

The counts come from the plain reference's loss-and-gradient function traced
at the cell's shapes (`jax.make_jaxpr` over shapes: nothing runs), never from
the program's jaxpr, XLA's cost model or the trace's own statistics. So a
change of lowering in the program (a Pallas call, a fusion, padding, remat)
cannot move them. The conv and dot arithmetic is copied from
`pytorchvideo_accelerate_tpu/analysis/gc_flops.py` (the original stays).

Per contraction (a forward conv, its data-gradient conv, its weight-gradient
conv, a matmul) the work is
    flops = 2 * multiply-adds that land on real input elements
    bytes = each operand read once and the result written once, at the
            width the configuration computes in (`bytes_per_element`)
and its least time on a chip is max(flops / peak_flops, bytes / peak_bw).
The least times of a class add up: the contractions run one after another.
"""

from __future__ import annotations

import jax

CLASSES = ("conv_dense", "conv_depthwise", "dot")


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _valid_taps(out_size, k, stride, pad_lo, lhs_dil, rhs_dil, in_size):
    """Multiply-adds along one spatial dim that land on real input elements
    (padding and the zeros of a dilated operand cost nothing)."""
    span = (in_size - 1) * lhs_dil + 1
    taps = 0
    for o in range(out_size):
        base = o * stride - pad_lo
        for d in range(k):
            p = base + d * rhs_dil
            if 0 <= p < span and p % lhs_dil == 0:
                taps += 1
    return taps


def conv_flops(eqn):
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    strides = eqn.params["window_strides"]
    padding = eqn.params["padding"]
    lhs_dil = eqn.params.get("lhs_dilation") or (1,) * len(strides)
    rhs_dil = eqn.params.get("rhs_dilation") or (1,) * len(strides)
    taps = 1
    for i, (ld, rd) in enumerate(zip(dn.lhs_spec[2:], dn.rhs_spec[2:])):
        taps *= _valid_taps(out.shape[dn.out_spec[2 + i]], rhs.shape[rd],
                            strides[i], padding[i][0], lhs_dil[i], rhs_dil[i],
                            lhs.shape[ld])
    batch = out.shape[dn.out_spec[0]]
    c_out = out.shape[dn.out_spec[1]]
    c_in_per_group = rhs.shape[dn.rhs_spec[1]]
    return 2.0 * batch * c_out * c_in_per_group * taps


def dot_flops(eqn):
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = _prod(lhs.shape[d] for d in lb)
    contract = _prod(lhs.shape[d] for d in lc)
    m = _prod(lhs.shape[d] for d in range(lhs.ndim) if d not in set(lc) | set(lb))
    n = _prod(rhs.shape[d] for d in range(rhs.ndim) if d not in set(rc) | set(rb))
    return 2.0 * batch * m * n * contract


def _operand_elements(eqn):
    return (sum(_prod(v.aval.shape) for v in eqn.invars[:2])
            + _prod(eqn.outvars[0].aval.shape))


def _sub_jaxprs(value):
    from jax._src import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def contractions(closed_jaxpr, dense_scope, depthwise_scope):
    """Every conv and dot of the jaxpr as (class, flops, operand elements)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "conv_general_dilated":
                stack = str(eqn.source_info.name_stack)
                if depthwise_scope in stack:
                    cls = "conv_depthwise"
                elif dense_scope in stack:
                    cls = "conv_dense"
                else:
                    raise ValueError(f"conv outside both scopes: {stack!r}")
                found.append((cls, conv_flops(eqn), _operand_elements(eqn)))
            elif name == "dot_general":
                found.append(("dot", dot_flops(eqn), _operand_elements(eqn)))
            elif name in ("scan", "while", "cond"):
                raise ValueError(f"{name} in the reference's jaxpr: the plain "
                                 "reference has no loops to count")
            else:
                for value in eqn.params.values():
                    for sub in _sub_jaxprs(value):
                        walk(sub)

    walk(closed_jaxpr.jaxpr)
    return found


def work(found, peaks, bytes_per_element):
    """Per class: flops, least bytes, least seconds on the chip and how many
    of the contractions the memory bound (rather than the compute bound) sets."""
    out = {c: {"flops": 0.0, "bytes": 0.0, "least_s": 0.0, "n": 0,
               "memory_bound": 0} for c in CLASSES}
    for cls, flops, elements in found:
        nbytes = elements * bytes_per_element
        t_flops = flops / peaks["bf16_flops_per_s"]
        t_bytes = nbytes / peaks["hbm_bytes_per_s"]
        o = out[cls]
        o["flops"] += flops
        o["bytes"] += nbytes
        o["least_s"] += max(t_flops, t_bytes)
        o["n"] += 1
        o["memory_bound"] += t_bytes > t_flops
    return out


def reference_work(family_name, arch, batch_shapes, peaks, bytes_per_element=2):
    """The work of one optimizer step's forward and backward pass at
    `batch_shapes` ({name: (shape, dtype)}), from the reference (no remat:
    recomputed operations are not work)."""
    import jax.numpy as jnp

    from benchmarks import reference
    from benchmarks.reference import plain

    fam = reference.family(family_name)
    variables = jax.eval_shape(
        lambda: reference.init_variables(family_name, arch, 0))
    batch = {k: jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
             for k, (s, d) in batch_shapes.items()}

    def loss_fn(params, batch):
        net = plain.Net({"params": params})
        logits = fam.forward(net, batch, arch, remat=False)
        return plain.cross_entropy(logits, batch["label"])

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss_fn))(variables["params"], batch)
    found = contractions(jaxpr, plain.DENSE_SCOPE, plain.DEPTHWISE_SCOPE)
    by_class = work(found, peaks, bytes_per_element)
    total = sum(c["flops"] for c in by_class.values())
    return {"flops_per_step": total, "by_class": by_class}
