"""Distributed runtime (reference /root/reference/unicore/distributed/utils.py).

TPU-native redesign: the reference's NCCL process groups, torchrun spawning and
pickle-over-byte-tensor collectives are replaced by
``jax.distributed.initialize`` (coordinator rendezvous), a
``jax.sharding.Mesh`` over ICI/DCN whose collectives XLA emits from sharding
annotations, and ``multihost_utils`` host-level broadcasts.  One process per
host; per-device parallelism is SPMD inside jit, so there is no
process-per-GPU spawn boundary (reference utils.py:147-189) to reproduce.
"""

import logging
import os
import socket
from argparse import Namespace
from typing import Any, Dict, Optional

import numpy as np

import jax

from unicore_tpu.distributed import guard

logger = logging.getLogger(__name__)

_initialized = False


def _timed(name, fn, geometry=None, local=None):
    """Run one host collective under the watchdog (guard.run_collective):
    with ``--collective-timeout`` set, a stalled peer turns into a
    diagnosed abort (thread stacks + last fingerprint) instead of an
    infinite hang.  ``geometry`` (payload shape/dtype for geometry-rigid
    collectives) rides the ``--sanitize-collectives`` fingerprint
    exchange so crossed payloads are named BEFORE the collective runs;
    ``local`` is this wrapper's single-process value, returned when a
    chaos ``collective-order-skew`` skip makes this rank behave as if it
    never reached the collective."""
    return guard.run_collective(name, fn, geometry=geometry, local=local)


def infer_init_method(args):
    """Infer the coordinator address (reference utils.py:32-106): explicit
    flag > torchrun-style env (MASTER_ADDR/PORT) > SLURM > single host."""
    if args.distributed_init_method is not None:
        return args.distributed_init_method
    if all(k in os.environ for k in ["MASTER_ADDR", "MASTER_PORT"]):
        return "{}:{}".format(os.environ["MASTER_ADDR"], os.environ["MASTER_PORT"])
    if "SLURM_NODELIST" in os.environ and os.environ.get("SLURM_NNODES", "1") != "1":
        try:
            import subprocess

            node_list = os.environ["SLURM_NODELIST"]
            hostnames = subprocess.check_output(
                ["scontrol", "show", "hostnames", node_list]
            )
            host = hostnames.split()[0].decode("utf-8")
            port = args.distributed_port if args.distributed_port > 0 else 12355
            return f"{host}:{port}"
        except Exception:
            return None
    return None


def distributed_init(args) -> int:
    """Initialize the multi-host runtime (reference utils.py:109-144).

    Safe to call on a single host (no-op).  Returns the process index.
    """
    global _initialized
    coordinator = infer_init_method(args)
    num_processes = int(
        os.environ.get("SLURM_NNODES", os.environ.get("WORLD_SIZE", "1"))
    )
    if coordinator is not None and num_processes > 1 and not _initialized:
        process_id = int(
            os.environ.get("SLURM_PROCID", os.environ.get("RANK", "0"))
        )
        logger.info(
            f"initializing jax.distributed: coordinator={coordinator} "
            f"process={process_id}/{num_processes}"
        )
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # multi-process CPU runs (virtual-mesh smoke tests, CI) need the
            # gloo collectives backend — the default CPU client refuses
            # cross-process computations outright.  Checked via the env var:
            # probing jax.default_backend() here would initialize the
            # backend before jax.distributed.initialize.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        init_kwargs = {}
        try:
            # elastic restarts bound the rendezvous: a re-formed membership
            # that cannot assemble (a peer really is gone) must fail fast
            # and return control to the supervisor, not burn 300s per
            # attempt (distributed/elastic.py sets this for its children)
            rdv = int(os.environ.get("UNICORE_TPU_RENDEZVOUS_TIMEOUT", "0"))
            if rdv > 0:
                init_kwargs["initialization_timeout"] = rdv
        except ValueError:
            pass
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            **init_kwargs,
        )
        _initialized = True
    args.distributed_rank = jax.process_index()
    args.distributed_world_size = jax.device_count()
    return args.distributed_rank


def call_main(args, main, **kwargs):
    """Entry point (reference utils.py:166-189).  JAX is single-process per
    host, so no spawn: initialize the cluster (if any) and call main.

    ``--suppress-crashes`` (reference options.py): swallow training
    exceptions and return None instead of propagating, so sweep drivers
    that call this in-process get a return value per trial rather than an
    abort.  KeyboardInterrupt always propagates.
    """
    distributed_init(args)
    if not getattr(args, "suppress_crashes", False):
        return main(args, **kwargs)
    try:
        return main(args, **kwargs)
    except KeyboardInterrupt:
        raise
    except Exception:
        logger.exception(
            "training crashed; continuing because --suppress-crashes is set"
        )
        return None


# ---------------------------------------------------------------------------
# topology queries (reference utils.py:203-233 — process-group getters)
# ---------------------------------------------------------------------------

def get_data_parallel_group():
    """Kept for API parity; sharding specs replace process groups."""
    return None


def get_data_parallel_rank() -> int:
    """This PROCESS's rank among data-parallel workers — the reference's
    meaning (utils.py:226: one process per GPU, rank == process rank), kept
    so user-dir plugins doing ``rank == 0`` guards or
    ``data[rank::world_size]`` arithmetic against
    :func:`get_data_parallel_world_size` keep working.  Device-granular
    sharding (a JAX process drives several chips) lives in the explicitly
    named :func:`get_data_parallel_shard_index` /
    :func:`get_data_parallel_num_shards` pair; meshed trainers use
    ``Trainer.data_parallel_rank``, which also accounts for non-data mesh
    axes."""
    return jax.process_index()


def get_data_parallel_world_size() -> int:
    """Number of data-parallel worker PROCESSES (pairs with
    :func:`get_data_parallel_rank`)."""
    return jax.process_count()


def get_data_parallel_shard_index() -> int:
    """Index of this process's FIRST device among all data-parallel device
    shards (device-granular; pairs with
    :func:`get_data_parallel_num_shards`)."""
    return jax.process_index() * jax.local_device_count()


def get_data_parallel_num_shards() -> int:
    """Total data-parallel device shards (device-granular)."""
    return jax.device_count()


def get_pod_count() -> int:
    """Number of pods the ParallelPlan declares (the DCN tier of the dp
    dimension, ``--num-pods``); 1 when no plan is published or the plan
    is single-pod."""
    from unicore_tpu.parallel import get_global_plan

    plan = get_global_plan()
    return plan.pods if plan is not None else 1


def get_pod_index() -> int:
    """Which pod this process's FIRST device lives in, under the plan's
    mesh layout ('pod' is the outermost axis, so pod p owns the
    contiguous device block [p * devices_per_pod, (p+1) *
    devices_per_pod)).  0 on single-pod plans — rank-0-of-pod-0 guards
    degrade to plain rank-0 guards."""
    pods = get_pod_count()
    if pods <= 1:
        return 0
    devices_per_pod = max(1, jax.device_count() // pods)
    return (jax.process_index() * jax.local_device_count()) // devices_per_pod


def get_global_rank() -> int:
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def is_master(args) -> bool:
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# host-level collectives (reference utils.py:236-495).  Inside jit, data
# collectives are emitted by XLA from shardings; these host-level helpers
# cover the control plane (checkpoint metadata, logging gathers).
# ---------------------------------------------------------------------------

def all_reduce(tensor, op="sum"):
    """Host-level all-reduce of a small array across processes."""
    if jax.process_count() == 1:
        return tensor
    arr = np.asarray(tensor)
    return _timed(
        "all_reduce",
        lambda: _all_reduce_impl(arr, op),
        geometry=f"shape={tuple(arr.shape)} dtype={arr.dtype} op={op}",
        local=lambda: arr,
    )


def _all_reduce_impl(tensor, op):
    from jax.experimental import multihost_utils

    arr = np.asarray(tensor)
    summed = multihost_utils.process_allgather(arr)
    if op == "sum":
        return summed.sum(axis=0)
    elif op == "max":
        return summed.max(axis=0)
    elif op == "min":
        return summed.min(axis=0)
    else:
        raise ValueError(f"unsupported op {op}")


def all_gather_list(data, group=None, max_size=None):
    """Gather arbitrary picklable data from all hosts
    (reference utils.py:275-349 — pickle over a byte tensor; here
    multihost_utils handles the byte plumbing).

    With ``max_size=None`` (default) the buffer is auto-sized in two phases:
    an 8-byte length gather first, then a payload gather padded to the
    LARGEST host's length — so payloads of any size work and small payloads
    never pay for a large fixed buffer.  Passing ``max_size`` keeps the
    reference's single-round fixed-buffer behavior (one collective instead
    of two; errors if the payload doesn't fit).

    A row that fails to unpickle is NOT re-raised raw: it means that peer
    is executing a DIFFERENT collective (out-of-sync workers — the
    reference's utils.py:340-349 signal), so it surfaces as a
    :class:`~unicore_tpu.distributed.guard.DesyncError` naming the rank."""
    if jax.process_count() == 1:
        return [data]
    return _timed(
        "all_gather_list",
        lambda: _all_gather_list_impl(data, max_size),
        local=lambda: [data],
    )


def _all_gather_list_impl(data, max_size):
    import pickle

    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(data), dtype=np.uint8)
    if max_size is not None:
        if len(payload) > max_size - 8:
            raise ValueError(
                f"encoded data size ({len(payload)}) exceeds max_size ({max_size})"
            )
        pad_to = max_size - 8
    else:
        lengths = multihost_utils.process_allgather(
            np.asarray([len(payload)], dtype=np.uint64)
        )
        pad_to = int(np.asarray(lengths).max())
    buf = np.zeros((8 + pad_to,), dtype=np.uint8)
    header = np.frombuffer(
        np.asarray([len(payload)], dtype=np.uint64).tobytes(), dtype=np.uint8
    )
    buf[:8] = header
    buf[8 : 8 + len(payload)] = payload
    gathered = multihost_utils.process_allgather(buf)
    return _decode_gathered_rows(gathered)


def _decode_gathered_rows(gathered):
    """Decode each rank's length-prefixed pickle row; an undecodable row is
    diagnosed as that rank being out of sync rather than a raw traceback."""
    import pickle

    out = []
    for rank, row in enumerate(gathered):
        row = np.asarray(row, dtype=np.uint8)
        try:
            n = int(np.frombuffer(row[:8].tobytes(), dtype=np.uint64)[0])
            if n > len(row) - 8:
                raise ValueError(
                    f"length header {n} exceeds buffer ({len(row) - 8})"
                )
            out.append(pickle.loads(row[8 : 8 + n].tobytes()))
        except Exception as e:
            raise guard.DesyncError(
                f"all_gather_list: could not decode the payload from rank "
                f"{rank} ({type(e).__name__}: {e}).  That rank is most "
                "likely executing a DIFFERENT collective — workers are out "
                "of sync (divergent control flow, crash-restart, or a "
                "desynced step counter on that host)."
            ) from e
    return out


def all_reduce_dict(data: Dict[str, Any], device=None, group=None) -> Dict[str, Any]:
    """Sum-reduce a flat dict of scalars across hosts
    (reference utils.py:352-398)."""
    if jax.process_count() == 1:
        return dict(data)
    keys = sorted(data.keys())
    vec = np.asarray([float(data[k]) for k in keys], dtype=np.float64)
    out = _timed(
        "all_reduce_dict",
        lambda: _all_reduce_impl(vec, "sum"),
        # the key SET is the geometry: a host carrying a different metric
        # set would silently mis-pair every scalar after the mismatch
        geometry=f"keys={','.join(keys)}",
        local=lambda: vec,
    )
    return {k: out[i] for i, k in enumerate(keys)}


def _as_bytes(arr):
    """Flat uint8 view of an array's buffer — the only dtype
    ``multihost_utils`` moves losslessly under the default x64-disabled
    config (int64/float64 payloads would be silently canonicalized to
    32-bit; same workaround as broadcast_object's length header)."""
    return np.frombuffer(np.ascontiguousarray(arr).tobytes(), dtype=np.uint8)


def _from_bytes(buf, shape, dtype):
    return np.frombuffer(
        np.asarray(buf, dtype=np.uint8).tobytes(), dtype=dtype
    ).reshape(shape)


def all_to_all(tensor, group=None):
    """Host-level all-to-all: row block i of this host's array is delivered
    to host i; the result holds one row block from every host
    (reference utils.py:251-259 — dist.all_to_all_single).

    The input's leading dim must be divisible by the process count.  Built on
    one allgather + a local slice: host j keeps block j of every gathered
    row.  In-jit data-plane all-to-alls are emitted by XLA from shardings
    (or ``lax.all_to_all`` inside shard_map); this helper covers host-side
    control-plane use only.
    """
    arr = np.asarray(tensor)
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils

    n = jax.process_count()
    if arr.shape[0] % n != 0:
        raise ValueError(
            f"all_to_all leading dim {arr.shape[0]} not divisible by "
            f"process count {n}"
        )
    rows = arr.shape[0] // n
    me = jax.process_index()
    gathered = _timed(
        "all_to_all",
        lambda: multihost_utils.process_allgather(_as_bytes(arr)),
        geometry=f"shape={tuple(arr.shape)} dtype={arr.dtype}",
        # the skip fallback must still satisfy the (n, bytes) contract
        # the slicing below consumes — n copies of the local payload
        local=lambda: np.stack([_as_bytes(arr)] * n),
    )  # (n, bytes)
    return np.concatenate(
        [
            _from_bytes(gathered[src], arr.shape, arr.dtype)[
                me * rows : (me + 1) * rows
            ]
            for src in range(n)
        ],
        axis=0,
    )


def broadcast_tensors(tensors, src_rank=0, group=None, dist_device=None):
    """Broadcast a list of arrays from one host; non-source hosts pass None
    and receive the values (reference utils.py:406-445 — shape/dtype
    metadata first, then each tensor)."""
    if jax.process_count() == 1:
        return tensors
    return _timed(
        "broadcast_tensors",
        lambda: _broadcast_tensors_impl(tensors, src_rank),
        local=lambda: tensors,
    )


def _broadcast_tensors_impl(tensors, src_rank):
    from jax.experimental import multihost_utils

    is_source = jax.process_index() == src_rank
    meta = (
        [
            (tuple(np.asarray(t).shape), np.dtype(np.asarray(t).dtype).name)
            for t in tensors
        ]
        if is_source
        else None
    )
    meta = _broadcast_object_impl(meta, src_rank)
    out = []
    for i, (shape, dtype) in enumerate(meta):
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        buf = (
            _as_bytes(np.asarray(tensors[i]))
            if is_source
            else np.zeros((nbytes,), dtype=np.uint8)
        )
        got = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
        out.append(_from_bytes(got, shape, dtype))
    return out


def broadcast_object(obj, src_rank=0, group=None):
    """Broadcast a picklable object from one host to all
    (reference utils.py:447-495).

    Only the source rank needs to supply ``obj`` (others pass anything);
    the payload travels as bytes in two phases — length, then buffer — so
    pytree structures never need to match across hosts (passing mismatched
    structures to ``broadcast_one_to_all`` directly deadlocks).
    """
    if jax.process_count() == 1:
        return obj
    return _timed(
        "broadcast_object",
        lambda: _broadcast_object_impl(obj, src_rank),
        local=lambda: obj,
    )


def _broadcast_object_impl(obj, src_rank):
    import pickle

    from jax.experimental import multihost_utils

    is_source = jax.process_index() == src_rank
    if is_source:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    else:
        payload = np.zeros((0,), dtype=np.uint8)
    # length travels as 8 uint8 bytes: an int64 array would be silently
    # canonicalized to int32 under the default x64-disabled config, wrapping
    # for payloads >= 2 GiB (same encoding as all_gather_list's header)
    header = np.frombuffer(
        np.asarray([len(payload)], dtype=np.uint64).tobytes(), dtype=np.uint8
    )
    n_bytes = multihost_utils.broadcast_one_to_all(header, is_source=is_source)
    n = int(np.frombuffer(np.asarray(n_bytes, dtype=np.uint8).tobytes(),
                          dtype=np.uint64)[0])
    buf = payload if is_source else np.zeros((n,), dtype=np.uint8)
    out = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
    try:
        # the explicit uint8 cast is load-bearing: broadcast_one_to_all is
        # a psum under the hood and some backends (gloo CPU collectives)
        # return the accumulator dtype (uint32) — .tobytes() on that would
        # interleave zero bytes into the pickle stream
        return pickle.loads(np.asarray(out, dtype=np.uint8).tobytes())
    except Exception as e:
        raise guard.DesyncError(
            f"broadcast_object: could not decode the payload from source "
            f"rank {src_rank} ({type(e).__name__}: {e}) — this host is most "
            "likely out of sync with the source (executing a different "
            "collective)."
        ) from e


def barrier(tag: str = "barrier") -> None:
    """Watchdog-timed host barrier (``sync_global_devices``): all hosts
    must reach the same ``tag`` — with ``--collective-timeout`` set, a
    missing peer raises a diagnosed :class:`CollectiveTimeoutError`
    instead of hanging forever."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    _timed(
        f"barrier:{tag}",
        lambda: multihost_utils.sync_global_devices(tag),
        local=lambda: None,
    )
