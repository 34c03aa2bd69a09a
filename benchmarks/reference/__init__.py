"""The plain references: one module per family, found by name.

A family module gives `forward(net, batch, arch, remat)`, `init_batch(arch)`,
`inputs(batch)`, `expected_inputs(arch, batch, frames, crop)` and
`derived_inputs(batch, arch)`. This file adds what every family shares:
making the seeded weights in one jitted call, and following the first
optimizer steps (loss, gradients, SGD update) in float32.
"""

from __future__ import annotations

import importlib
import json

import jax
import jax.numpy as jnp

from . import plain


def family(name):
    return importlib.import_module(f"{__name__}.{name}")


def init_variables(family_name, arch, seed):
    """{"params", "batch_stats"} from the seed, made on the device in one
    jitted call. The same call gives the same leaves to the program and,
    later, to the reference."""
    fam = family(family_name)

    def make(key):
        net = plain.Net(key=key)
        fam.forward(net, fam.init_batch(arch), arch)
        return net.variables()

    return jax.jit(make)(jax.random.key(int(seed) % (2 ** 31)))


_STEPS = {}


def make_step(family_name, arch, optim, q=None, fault=None):
    """The jitted reference step, built once per process for each variant."""
    key = json.dumps([family_name, arch, optim, q, fault], sort_keys=True)
    if key not in _STEPS:
        _STEPS[key] = _make_step(family_name, arch, optim, q, fault)
    return _STEPS[key]


def _make_step(family_name, arch, optim, q, fault):
    """One jitted optimizer step of the reference:
    (params, momentum, batch, step) -> (params, momentum, loss, grads).

    `fault="half_batch"` plants a fault the `correct` test has to catch: the
    second half of the rows left out, the mean taken over the rest. `q` is
    the control's precision (see plain.quantize)."""
    fam = family(family_name)

    def loss_fn(params, batch):
        if fault == "half_batch":
            half = batch["label"].shape[0] // 2
            batch = {k: v[:half] for k, v in batch.items()}
        net = plain.Net({"params": params}, q=q)
        logits = fam.forward(net, batch, arch)
        return plain.cross_entropy(logits, batch["label"])

    def step(params, buf, batch, count):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new, buf = plain.sgd_update(params, buf, grads, count, optim)
        return new, buf, loss, grads

    return jax.jit(step, donate_argnums=(1,))


def follow(family_name, arch, optim, params, batches, q=None, fault=None,
           note=None):
    """Drive the reference through `batches` from `params` (committed to
    one device, as the batches are, so that the step compiles once). Returns
    the losses, the first step's gradient norms by leaf, the norms of the
    parameters' change over all the steps by leaf (host floats) and the
    leaves' numbers of elements.
    `fault="state_unchanged"` keeps the first state through every step."""
    step = make_step(family_name, arch, optim, q=q,
                     fault=None if fault == "state_unchanged" else fault)
    norms = jax.jit(plain.leaf_norms)
    start = params
    buf = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        new, new_buf, loss, grads = step(params, buf, batch, jnp.int32(i))
        if fault == "state_unchanged":
            buf = jax.tree.map(jnp.zeros_like, params)  # the old one was donated
        else:
            params, buf = new, new_buf
        del new, new_buf
        losses.append(float(loss))
        if note:
            note(f"reference step {i + 1} done")
        if i == 0:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
        del grads
    delta = jax.jit(lambda a, b: plain.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))(params, start)
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path): int(leaf.size)
             for path, leaf in jax.tree_util.tree_flatten_with_path(start)[0]}
    return {"losses": losses, "grad_norms": grad_norms, "sizes": sizes,
            "delta_norms": {k: float(v) for k, v in delta.items()}}
