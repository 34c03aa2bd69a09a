"""Collective helpers.

The reference funnels collectives through a Python facade
(`accelerate/utils/operations.py:322-357` gather, `accelerator.py:3141,3178`
reduce/broadcast) calling NCCL. TPU-native, in-graph collectives are just
`lax.psum/pmean/all_gather/ppermute` under `shard_map` — XLA schedules them on
ICI. This module keeps (a) thin in-graph wrappers for code written with
`shard_map`, and (b) host-level out-of-band helpers for metric fetch across
processes.

Note the design inversion for metrics: the reference gathers *per-sample*
predictions to every rank and feeds a stateful torchmetrics object
(run.py:298) — which double-counts DistributedSampler padding (SURVEY §2.1).
Here eval metrics are accumulated inside the compiled step as (correct, total)
sums over the sharded batch (trainer/metrics.py), so there is nothing to
gather and no padding bias.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def psum(x: Any, axis_name) -> Any:
    return lax.psum(x, axis_name)


def pmean(x: Any, axis_name) -> Any:
    return lax.pmean(x, axis_name)


def all_gather(x: Any, axis_name, axis: int = 0, tiled: bool = True) -> Any:
    """`accelerator.gather` equivalent for shard_map code paths.

    Note (jax >= 0.8 shard_map varying-mesh-axes checking): the gathered
    value is identical on every shard but still *tracked* as varying over
    `axis_name`, so returning it directly with `out_specs=P()` fails the
    static replication check. Either consume it inside the shard_map (the
    usual case — e.g. ring attention), or pass `check_vma=False` to
    `jax.shard_map` when you really want the replicated gather as an
    output (tested in tests/test_mesh_sharding.py)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute_ring(x: Any, axis_name, shift: int = 1) -> Any:
    """Rotate values around the mesh axis ring (ring-attention building block)."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def host_allgather(x: Any) -> Any:
    """Out-of-band cross-process gather (DCN), for host-side logging only.

    Watched: a straggler host wedges every peer inside this call, so it
    runs under the collective-hang detector's attributed section
    (`parallel/hangcheck.py`) — a stall dumps `host_allgather host=i/n`
    instead of anonymous silence."""
    from pytorchvideo_accelerate_tpu.parallel.hangcheck import (
        collective_section,
    )

    with collective_section("host_allgather"):
        if jax.process_count() == 1:
            return jax.tree.map(lambda a: jnp.asarray(a)[None], x)
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(x)


def host_broadcast(x: Any) -> Any:
    """`accelerator.broadcast` equivalent, host level: every process returns
    process 0's value (run-name, resolved checkpoint path, sampled seed —
    anything one host decides for all).

    Numeric leaves ride `multihost_utils.broadcast_one_to_all` and come back
    as numpy arrays on EVERY process — including single-process runs, so dev
    and pod behavior can't diverge. str/bytes leaves (which psum-based
    broadcast can't carry) are broadcast as length then a uint8 buffer and
    come back as str/bytes.

    Watched (`parallel/hangcheck.py`): the resume-time broadcast is the
    classic place a pod wedges when one host's checkpoint scan hangs —
    the hang detector attributes it per host instead of letting the
    external timeout kill blind."""
    from jax.experimental import multihost_utils

    from pytorchvideo_accelerate_tpu.parallel.hangcheck import (
        collective_section,
    )

    bcast_raw = multihost_utils.broadcast_one_to_all

    def bcast(v):
        with collective_section("host_broadcast"):
            return bcast_raw(v)

    leaves, treedef = jax.tree.flatten(x)
    out = list(leaves)
    num_idx = [i for i, v in enumerate(leaves)
               if not isinstance(v, (str, bytes))]
    str_idx = [i for i, v in enumerate(leaves) if isinstance(v, (str, bytes))]
    # one collective for ALL numeric leaves (broadcast_one_to_all takes a
    # pytree) + one for all string lengths; only the string buffers (rare)
    # need a round trip each, since their shapes depend on root's lengths
    if num_idx:
        nums = bcast([leaves[i] for i in num_idx])
        for i, v in zip(num_idx, nums):
            out[i] = np.asarray(v)
    if str_idx:
        raws = [leaves[i].encode("utf-8") if isinstance(leaves[i], str)
                else bytes(leaves[i]) for i in str_idx]
        lens = [int(n) for n in np.asarray(
            bcast(np.array([len(r) for r in raws], np.int64)))]
        for i, raw, n in zip(str_idx, raws, lens):
            buf = np.zeros(n, np.uint8)
            data = np.frombuffer(raw, np.uint8)
            buf[: min(data.size, n)] = data[:n]
            res = bytes(np.asarray(bcast(buf), np.uint8))
            out[i] = (res.decode("utf-8") if isinstance(leaves[i], str)
                      else res)
    return jax.tree.unflatten(treedef, out)


def host_reduce_sum(x: Any) -> Any:
    """`accelerator.reduce(op="sum")` equivalent for host-side counters
    (clips decoded, batches dropped): sums each leaf across processes."""
    gathered = host_allgather(x)
    return jax.tree.map(lambda a: a.sum(axis=0), gathered)
