"""Serve-startup calibration: per-channel weight scales + per-site
activation scales, persisted beside the snapshot, re-verified on reload.

The flow (docs/serving.md, "Quantized inference"):

1. **collect** — :func:`collect_scales` runs deterministic held-out
   batches (one per warmed bucket edge, token ids from a fixed-seed
   stream) through the model's fp32 path inside
   :func:`~unicore_tpu.quant.calibration_scope`; every ``QuantDense``
   site built with a mode sows its input absmax (and output absmax for ``quantize_output``
   sites) into the ``quant_calib`` collection with a running-max reducer.
   Same batches => bit-identical scales (the determinism test proves it).
2. **prepare** — :func:`prepare` transforms the fp32 checkpoint tree:
   each site's ``kernel`` becomes ``kernel_q`` (int8/fp8, per-OUTPUT-
   channel symmetric) + ``kernel_scale``; the calibrated ``act_scale``
   [+ ``out_scale``] land beside them.  The result is the tree the
   quantized per-bucket programs serve from.
3. **persist** — :func:`save_scales` writes the activation scales plus a
   SHA-256 digest of the site weights beside the snapshot
   (``<snapshot>.quant-scales.json``).  Hot reload re-uses them only when
   the candidate's digest matches (:func:`load_scales` +
   :func:`digest_matches`); otherwise it re-derives by re-running this
   pass on the candidate — and ANY failure here is a named
   ``rejected:calibration`` rollback, never a swap.
4. **drift** — :func:`logit_drift` runs the same batches through both
   precision paths and reports max/mean absolute logit drift (the
   documented error-bound contract; journaled as the ``quant-path`` kind).
"""

import hashlib
import json
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from unicore_tpu import quant as _q
from unicore_tpu.quant.dense import CALIB_COLLECTION

logger = logging.getLogger(__name__)

SCALES_SUFFIX = ".quant-scales.json"
SCALES_VERSION = 1

#: scale floor: an all-zero calibration activation must quantize to
#: zeros, not divide by zero
SCALE_FLOOR = 1e-8


class CalibrationError(RuntimeError):
    """Calibration/scale verification failed — on the hot-reload path
    this is a named rollback (``rejected:calibration``), never a swap."""


def scales_path(snapshot_path: str) -> str:
    return snapshot_path + SCALES_SUFFIX


# ---------------------------------------------------------------------------
# deterministic held-out batches
# ---------------------------------------------------------------------------

def calibration_batches(
    vocab_size: int,
    pad_idx: int,
    bucket_edges: Sequence[int],
    batch_size: int,
    n_batches: int = 1,
    seed: int = 0,
) -> List[np.ndarray]:
    """One deterministic ``(batch_size, edge)`` int32 token batch per
    bucket edge (times ``n_batches`` rounds) — the calibration inputs
    exercise every warmed program geometry, and the fixed seed makes the
    resulting scales a pure function of the weights."""
    rng = np.random.RandomState(int(seed))
    lo = min(max(pad_idx + 1, 4), max(vocab_size - 1, 1))
    batches = []
    for _ in range(max(1, int(n_batches))):
        for edge in bucket_edges:
            batches.append(
                rng.randint(lo, vocab_size, size=(batch_size, int(edge)))
                .astype(np.int32)
            )
    return batches


# ---------------------------------------------------------------------------
# collect
# ---------------------------------------------------------------------------

def _flatten_calib(tree, prefix=()) -> Dict[str, Dict[str, float]]:
    """``quant_calib`` collection -> {site_path: {leaf: float}}; the leaf
    names (``act_absmax``/``out_absmax``) terminate each site path."""
    out: Dict[str, Dict[str, float]] = {}
    for key, val in tree.items():
        if isinstance(val, dict):
            out.update(_flatten_calib(val, prefix + (key,)))
        else:
            site = "/".join(prefix)
            out.setdefault(site, {})[key] = float(np.asarray(val))
    return out


def collect_scales(model, variables, batches: Sequence[np.ndarray],
                   ) -> Dict[str, Dict[str, float]]:
    """Run ``batches`` through the fp32 path with calibration sowing on;
    return ``{site_path: {'act_absmax': .., ['out_absmax': ..]}}`` with
    the running max merged across batches."""
    sites: Dict[str, Dict[str, float]] = {}
    with _q.calibration_scope():
        for tokens in batches:
            _, state = model.apply(
                variables, tokens, train=False,
                mutable=[CALIB_COLLECTION],
            )
            for site, leaves in _flatten_calib(
                state.get(CALIB_COLLECTION, {})
            ).items():
                slot = sites.setdefault(site, {})
                for name, value in leaves.items():
                    if not np.isfinite(value):
                        raise CalibrationError(
                            f"calibration produced a non-finite {name} at "
                            f"site {site} (poisoned weights?)"
                        )
                    slot[name] = max(slot.get(name, 0.0), value)
    if not sites:
        raise CalibrationError(
            "calibration saw no QuantDense sites — the model was not "
            "built with a quantize mode (or has no wired dense layers)"
        )
    return sites


# ---------------------------------------------------------------------------
# prepare: fp32 checkpoint tree -> quantized serving tree
# ---------------------------------------------------------------------------

def _site_node(params: dict, site: str) -> dict:
    node = params
    for part in site.split("/"):
        if not isinstance(node, dict) or part not in node:
            raise CalibrationError(
                f"calibrated site {site!r} not found in the checkpoint "
                "parameter tree (arch/config mismatch?)"
            )
        node = node[part]
    return node


def _quantize_weight(kernel: np.ndarray, qmax: float, dtype):
    w = np.asarray(kernel, dtype=np.float32)
    w_scale = np.maximum(np.abs(w).max(axis=0) / qmax, SCALE_FLOOR) \
        .astype(np.float32)
    v = np.clip(w / w_scale, -qmax, qmax)
    if dtype == np.int8:
        w_q = np.rint(v).astype(np.int8)
    else:
        import jax.numpy as jnp

        w_q = np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn))
    return w_q, w_scale


def prepare(variables, sites: Dict[str, Dict[str, float]], mode: str):
    """Build the quantized serving tree from the fp32 ``variables`` and
    the calibrated ``sites``: per site, ``kernel`` -> ``kernel_q`` +
    ``kernel_scale`` (per output channel), plus the activation scales.
    The fp32 tree is left untouched (a copy is transformed)."""
    import jax

    mode = _q.check_mode(mode)
    if mode == "off":
        return variables
    qmax = _q.QMAX[mode]
    np_dtype = np.int8 if mode == "int8" else None
    new_vars = jax.tree_util.tree_map(lambda x: x, variables)  # shallow-ish
    # tree_map rebuilds the dict spine, so in-place edits below never
    # touch the caller's fp32 tree
    params = new_vars["params"] if "params" in new_vars else new_vars
    for site, leaves in sorted(sites.items()):
        node = _site_node(params, site)
        if "kernel" not in node:
            raise CalibrationError(
                f"site {site!r} has no 'kernel' leaf to quantize"
            )
        kernel = node.pop("kernel")
        w_q, w_scale = _quantize_weight(kernel, qmax, np_dtype)
        node["kernel_q"] = w_q
        node["kernel_scale"] = w_scale
        node["act_scale"] = np.float32(
            max(leaves.get("act_absmax", 0.0) / qmax, SCALE_FLOOR)
        )
        if "out_absmax" in leaves:
            node["out_scale"] = np.float32(
                max(leaves["out_absmax"] / qmax, SCALE_FLOOR)
            )
    return new_vars


# ---------------------------------------------------------------------------
# persistence + re-verification
# ---------------------------------------------------------------------------

def weights_digest(variables, sites: Dict[str, Dict[str, float]]) -> str:
    """SHA-256 over the site kernels (sorted path order): scales are a
    pure function of (weights, calibration stream), so the digest ties a
    persisted scale set to the exact weights it was derived from."""
    params = variables["params"] if "params" in variables else variables
    h = hashlib.sha256()
    for site in sorted(sites):
        node = _site_node(params, site)
        kernel = node.get("kernel", node.get("kernel_q"))
        h.update(site.encode())
        h.update(np.ascontiguousarray(np.asarray(kernel)).tobytes())
    return h.hexdigest()


def save_scales(path: str, mode: str, sites: Dict[str, Dict[str, float]],
                digest: str, drift: Optional[dict] = None) -> None:
    """Persist beside the snapshot, atomically (stage + rename) so a
    reader never sees a torn scale file."""
    doc = {
        "version": SCALES_VERSION,
        "mode": mode,
        "weights_digest": digest,
        "sites": {k: dict(sorted(v.items())) for k, v in
                  sorted(sites.items())},
    }
    if drift is not None:
        doc["calibration_drift"] = drift
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def load_scales(path: str) -> Optional[dict]:
    """Read a persisted scale doc; None when absent, CalibrationError on
    a malformed/mismatched-version file (the reload path treats that as
    re-derive, not a crash)."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as err:
        raise CalibrationError(f"unreadable scale file {path}: {err}")
    if doc.get("version") != SCALES_VERSION or "sites" not in doc:
        raise CalibrationError(
            f"scale file {path} has unsupported version "
            f"{doc.get('version')!r}"
        )
    return doc


def digest_matches(doc: dict, variables) -> bool:
    return doc.get("weights_digest") == weights_digest(
        variables, doc.get("sites", {})
    )


# ---------------------------------------------------------------------------
# drift: the error-bound contract
# ---------------------------------------------------------------------------

def logit_drift(model_q, prepared, model_f32, variables,
                batches: Sequence[np.ndarray]) -> dict:
    """Max/mean absolute logit drift of the quantized path vs the fp32
    oracle over the calibration batches — the per-mode error bound the
    docs publish and the serve e2e asserts."""
    max_abs = 0.0
    mean_abs = 0.0
    ref_absmax = 0.0
    n = 0
    for tokens in batches:
        ref = np.asarray(
            model_f32.apply(variables, tokens, train=False),
            dtype=np.float32,
        )
        got = np.asarray(
            model_q.apply(prepared, tokens, train=False), dtype=np.float32
        )
        if not np.all(np.isfinite(got)):
            raise CalibrationError(
                "quantized forward produced non-finite logits on the "
                "calibration batch"
            )
        delta = np.abs(got - ref)
        max_abs = max(max_abs, float(delta.max()))
        mean_abs += float(delta.mean())
        ref_absmax = max(ref_absmax, float(np.abs(ref).max()))
        n += 1
    return {
        "max_abs_logit_drift": max_abs,
        "mean_abs_logit_drift": mean_abs / max(n, 1),
        "ref_logit_absmax": ref_absmax,
        "rel_drift": max_abs / max(ref_absmax, 1e-8),
        "batches": n,
    }


# ---------------------------------------------------------------------------
# the one-call serve-startup entry
# ---------------------------------------------------------------------------

def calibrate_for_serving(
    model_q, model_f32, variables, *,
    mode: str,
    snapshot_path: Optional[str],
    vocab_size: int,
    pad_idx: int,
    bucket_edges: Sequence[int],
    batch_size: int,
    n_batches: int = 1,
    persist: bool = True,
) -> Tuple[object, dict]:
    """Calibrate (or re-use persisted, digest-verified scales), prepare
    the quantized tree, measure drift, persist.  Returns
    ``(prepared_variables, info)`` where ``info`` carries the scale
    source, site count, drift stats, and the scales path.  Raises
    :class:`CalibrationError` on any failure — callers (startup, hot
    reload) decide whether that is fatal or a rollback."""
    mode = _q.check_mode(mode)
    if mode == "off":
        return variables, {"mode": "off"}
    path = scales_path(snapshot_path) if snapshot_path else None
    batches = calibration_batches(
        vocab_size, pad_idx, bucket_edges, batch_size, n_batches
    )
    sites = None
    source = "calibrated"
    if path:
        # a bad sidecar (torn write, old SCALES_VERSION, site naming a
        # param the candidate tree lacks) must never block serving a good
        # checkpoint: re-derive is always available one line below
        try:
            doc = load_scales(path)
            reusable = (
                doc is not None
                and doc.get("mode") == mode
                and digest_matches(doc, variables)
            )
        except CalibrationError as err:
            logger.warning(
                f"persisted quant scales at {path} are unusable "
                f"({err}) — re-calibrating"
            )
            doc, reusable = None, False
        if reusable:
            sites = doc["sites"]
            source = "reused-verified"
        elif doc is not None and doc.get("mode") == mode:
            logger.warning(
                f"persisted quant scales at {path} were derived from "
                "DIFFERENT weights (digest mismatch) — re-calibrating"
            )
    if sites is None:
        # collect through the QUANTIZE-AWARE model: calibration_scope
        # forces its QuantDense sites onto the fp path, but only model_q
        # knows which sites are quantize_output (they must sow out_absmax
        # or prepare() would leave their out_scale param missing)
        sites = collect_scales(model_q, variables, batches)
    prepared = prepare(variables, sites, mode)
    drift = logit_drift(model_q, prepared, model_f32, variables, batches)
    digest = weights_digest(variables, sites)
    if persist and path:
        try:
            save_scales(path, mode, sites, digest, drift)
        except OSError as err:
            logger.warning(
                f"could not persist quant scales to {path} ({err}); "
                "serving continues, the next start re-calibrates"
            )
            path = None
    info = {
        "mode": mode,
        "source": source,
        "sites": len(sites),
        "weights_digest": digest,
        "scales_path": path,
        **drift,
    }
    return prepared, info
