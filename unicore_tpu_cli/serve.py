#!/usr/bin/env python3
"""``unicore-tpu-serve``: the serving-plane entry point.

Boot sequence (each stage has a documented failure exit code — external
supervisors restart on these without log-grepping, same discipline as
the training taxonomy 65-74 in docs/robustness.md):

1. verified model load from ``--path`` (exit **76** on failure: missing
   file, corrupt checkpoint rejected by the integrity manifest, config
   that can't rebuild the model, or a warm-up that can't compile);
2. HTTP bind on ``--host:--port`` (exit **75** on failure) — probes go
   live immediately, readiness stays false;
3. bucket warm-up: one XLA program per bucket compiled (or reloaded from
   ``--jax-compilation-cache-dir``); readiness flips true only after;
4. serve until signalled: SIGTERM/SIGINT triggers a graceful drain —
   admission stops, in-flight batches flush under ``--drain-deadline``,
   exit **0**; a blown drain budget exits **77**; a second signal aborts
   immediately (also 77 — the drain did not complete cleanly).

``--reload-interval`` arms hot checkpoint reload (verify-then-swap with
rollback); ``--fault-inject`` arms the serving chaos kinds;
``--serve-quantize {int8,fp8}`` inserts a calibration pass before warm-up
and serves the quantized per-bucket programs (dequant fused into the
consuming ops; reload re-verifies scales and rolls back
``rejected:calibration`` on mismatch).  Decoder-only checkpoints (e.g.
``transformer_lm``) serve INCREMENTAL DECODE by default
(``--serve-decode``): a paged KV cache, a prefill/decode program split,
and step-level continuous batching behind ``POST /v1/generate``
(``--decode-kv int8`` halves cache bytes per token in flight).  ``--advertise`` +
``--fleet-kv`` joins a serving fleet: the replica self-registers
through a serve-namespaced heartbeat lease (address, readiness,
snapshot digest, /stats admission estimate), flips its lease ready
false the moment a drain begins, says a deregistration goodbye on
clean exit, and exposes ``POST /v1/reload`` for the router's rolling
reload.  See docs/serving.md.
"""

import json
import logging
import os
import signal
import sys
import threading
import time

_LOG_FIELDS = ("asctime", "levelname", "name", "message")
logging.basicConfig(
    stream=sys.stdout,
    level=os.environ.get("LOGLEVEL", "INFO").upper(),
    format=" | ".join(f"%({f})s" for f in _LOG_FIELDS),
    datefmt="%Y-%m-%d %H:%M:%S",
)
logger = logging.getLogger("unicore_tpu_cli.serve")

# serving exit-code taxonomy (documented in docs/robustness.md alongside
# the training codes 65-74)
EXIT_OK = 0
EXIT_SERVE_BIND = 75            # HTTP bind/port failure at startup
EXIT_SERVE_MODEL_LOAD = 76      # model load / warm-up failure at startup
EXIT_SERVE_DRAIN_DEADLINE = 77  # drain budget exceeded (or forced abort)
EXIT_SERVE_FLEET_KV = 78        # --advertise with an unusable --fleet-kv

SERVE_EXIT_CODE_NAMES = {
    EXIT_OK: "ok",
    EXIT_SERVE_BIND: "serve-bind-failure",
    EXIT_SERVE_MODEL_LOAD: "serve-model-load-failure",
    EXIT_SERVE_DRAIN_DEADLINE: "serve-drain-deadline-exceeded",
    EXIT_SERVE_FLEET_KV: "fleet-kv-failure",
}

# signal plumbing: first signal requests a drain, the second aborts
_drain_requested = threading.Event()
_signal_count = 0


def _handle_signal(signum, frame):
    global _signal_count
    _signal_count += 1
    name = signal.Signals(signum).name
    if _signal_count == 1:
        logger.warning(
            f"received {name}: graceful drain — admission stops, in-flight "
            "batches flush under --drain-deadline (second signal aborts)"
        )
        _drain_requested.set()
    else:
        logger.error(f"received second {name}: aborting without drain")
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(EXIT_SERVE_DRAIN_DEADLINE)


def load_serving_model(args):
    """Verified checkpoint load + model/task rebuild from the saved args.
    Any failure here is exit 76 territory — there is nothing to serve."""
    from unicore_tpu import checkpoint_utils, tasks

    state = checkpoint_utils.load_checkpoint_to_cpu(args.path)
    ckpt_args = state.get("args")
    if ckpt_args is None:
        raise ValueError(
            f"checkpoint {args.path} carries no saved args; cannot rebuild "
            "the model (was it written by an external tool?)"
        )
    if args.data:
        ckpt_args.data = args.data
    variables = state.get("model")
    if variables is None:
        raise ValueError(f"checkpoint {args.path} holds no model tree")
    task = tasks.setup_task(ckpt_args)
    model = task.build_model(ckpt_args)
    pad_idx = (
        task.dictionary.pad()
        if getattr(task, "dictionary", None) is not None
        else 0
    )
    eos_idx = (
        task.dictionary.eos()
        if getattr(task, "dictionary", None) is not None
        else 2
    )
    vocab_size = (
        len(task.dictionary)
        if getattr(task, "dictionary", None) is not None
        else int(getattr(model, "vocab_size", 0) or 0)
    )
    max_seq_len = int(getattr(ckpt_args, "max_seq_len", 512) or 512)
    hist = state.get("optimizer_history") or []
    step = hist[-1].get("num_updates", "?") if hist else "?"
    logger.info(
        f"serving model from {args.path} (step {step}, task "
        f"{type(task).__name__}, max_seq_len {max_seq_len})"
    )
    return model, variables, pad_idx, max_seq_len, vocab_size, eos_idx


def decode_serving_requested(args, model) -> bool:
    """``--serve-decode`` resolution: 'auto' turns the decode plane on
    exactly when the model exposes the serving surface (prefill +
    decode_step); 'on' demands it (exit-76 territory otherwise)."""
    mode = getattr(args, "serve_decode", "auto")
    has_surface = hasattr(model, "prefill") and hasattr(model, "decode_step")
    if mode == "off":
        return False
    if mode == "on" and not has_surface:
        raise ValueError(
            f"--serve-decode on: {type(model).__name__} has no "
            "prefill/decode_step surface; serve a decoder-only checkpoint "
            "(e.g. transformer_lm) or drop the flag"
        )
    return has_surface


def build_decode_engine(args, model, variables, pad_idx, max_seq_len,
                        vocab_size, eos_idx):
    """The incremental-decode engine (docs/serving.md 'Incremental
    decode'): cache-length buckets in page multiples, a paged KV pool
    sized by ``--cache-pages``, step-level continuous batching."""
    from unicore_tpu.serve import DecodeEngine, cache_bucket_edges

    if args.serve_quantize != "off":
        raise ValueError(
            "--serve-quantize is the encoder-path weight quantization; "
            "the decode plane quantizes its KV cache via --decode-kv int8 "
            "(use --serve-decode off to serve this checkpoint through the "
            "encoder path)"
        )
    edges = cache_bucket_edges(
        max_seq_len, args.serve_buckets, page_size=args.cache_page_size
    )
    return DecodeEngine(
        model, variables,
        bucket_edges=edges,
        decode_batch=args.decode_batch_size,
        prefill_batch=args.serve_batch_size,
        pad_idx=pad_idx,
        eos_idx=eos_idx,
        vocab_size=vocab_size,
        num_pages=args.cache_pages,
        page_size=args.cache_page_size,
        kv_dtype=args.decode_kv,
        max_new_tokens=args.max_new_tokens,
        admission_capacity=args.admission_capacity,
        precision="int8-kv" if args.decode_kv == "int8" else "",
        decode_sample_every=args.decode_sample_every,
    )


def serve_buckets(args, max_seq_len):
    from unicore_tpu.data.data_utils import compute_length_buckets

    return compute_length_buckets(args.serve_buckets, max_seq_len) or (
        max_seq_len,
    )


def setup_quantized_serving(args, model, variables, pad_idx, max_seq_len,
                            vocab_size, edges):
    """Startup calibration for ``--serve-quantize``: calibrate (or re-use
    digest-verified persisted scales), prepare the quantized tree, build
    the sampled drift probe and the hot-reload preparer.  Returns
    ``(model_q, prepared, quant_extras)`` — any failure here is exit-76
    territory (there is nothing safe to serve at the requested precision).
    """
    import jax
    import jax.numpy as jnp

    from unicore_tpu import telemetry
    from unicore_tpu.quant import calibrate

    mode = args.serve_quantize
    if vocab_size <= 0:
        raise ValueError(
            "--serve-quantize needs a vocabulary to synthesize calibration "
            "batches, but the task has no dictionary and the model reports "
            "no vocab_size"
        )
    if not hasattr(model, "quantize"):
        raise ValueError(
            f"--serve-quantize {mode}: {type(model).__name__} is not "
            "quantize-aware (no 'quantize' attr); only models whose dense "
            "call sites route through QuantDense can serve quantized"
        )
    model_q = model.clone(quantize=mode)
    prepared, info = calibrate.calibrate_for_serving(
        model_q, model, variables,
        mode=mode,
        snapshot_path=args.path,
        vocab_size=vocab_size,
        pad_idx=pad_idx,
        bucket_edges=edges,
        batch_size=args.serve_batch_size,
        n_batches=args.calibration_batches,
    )
    # prepare() hands back host (numpy) leaves; commit them to device ONCE
    # or every dispatch would re-transfer the whole tree
    prepared = jax.device_put(prepared)
    # the grep-able QUANT-PATH line + journal kind the CI smoke asserts on
    logger.info(
        f"QUANT-PATH {info['mode']}: scales {info['source']} for "
        f"{info['sites']} site(s), calibration max |logit drift| "
        f"{info['max_abs_logit_drift']:.5f} (rel {info['rel_drift']:.5f}) "
        f"over {info['batches']} batch(es); scales at {info['scales_path']}"
    )
    telemetry.emit(
        "quant-path", event="calibrated",
        **{k: v for k, v in info.items() if k != "weights_digest"},
    )

    # sampled per-request drift probe: its OWN jit (the engine's
    # recompile watchdog counts only the serving fn's cache).  The holder
    # keeps the (quantized, fp32) pair in lockstep with hot swaps: the
    # preparer stages a candidate pair, the engine's swap hook commits it
    # only when THAT prepared tree actually swaps in, and a probe-rejected
    # candidate's pair is released via preparer_abort — it neither leaks
    # device memory nor ever re-pairs the oracle.
    # the fp32 half of the pair is committed to device alongside the
    # prepared tree (only when sampling is on — it exists purely for the
    # oracle): a host-side tree would re-transfer the whole fp32 model
    # every sampled batch
    sampling = args.quant_drift_sample > 0
    oracle = {
        "q": prepared,
        "f": jax.device_put(variables) if sampling else variables,
        # candidate pairs staged by the preparer, committed by the swap
        # hook (engine loop thread) or released by preparer_abort (reload
        # thread) — hence the lock
        "staged": [],
    }
    oracle_lock = threading.Lock()

    @jax.jit
    def _drift(q_vars, f_vars, tokens):
        lq = model_q.apply(q_vars, tokens, train=False).astype(jnp.float32)
        lf = model.apply(f_vars, tokens, train=False).astype(jnp.float32)
        d = jnp.abs(lq - lf)
        # measure only where responses are cut from (ids[i, :len(r)]):
        # logits AT pad positions are never returned, and pad tokens are
        # outside the calibration distribution by construction — their
        # drift is real but irrelevant to any client
        if d.ndim >= 2 and tokens.ndim >= 2 \
                and d.shape[1] == tokens.shape[1]:
            real = (tokens != pad_idx).astype(jnp.float32)
            d = d * real.reshape(real.shape + (1,) * (d.ndim - 2))
        return jnp.max(d, axis=tuple(range(1, d.ndim)))

    def drift_probe(tokens):
        return _drift(oracle["q"], oracle["f"], tokens)

    if sampling:
        import numpy as np

        # pre-compile the shadow oracle for every warmed bucket geometry
        # NOW (startup, like engine warm-up): the first sampled batch per
        # shape would otherwise pay BOTH XLA compiles inside the live
        # serving loop, stalling batch formation past request deadlines
        for edge in edges:
            dummy = np.full(
                (args.serve_batch_size, int(edge)), pad_idx, np.int32
            )
            jax.block_until_ready(drift_probe(dummy))

    # filled by main() once the engine exists (the hook closure is built
    # before build_engine); the hook pushes the committed candidate's
    # calibration info into /stats
    engine_cell = {}

    def swap_hook(swapped_vars, tag):
        committed_info = None
        with oracle_lock:
            staged = oracle["staged"]
            for i, (q, f, new_info) in enumerate(staged):
                if q is swapped_vars:
                    oracle["q"], oracle["f"] = q, f
                    committed_info = new_info
                    # entries staged BEFORE the applied swap are
                    # superseded (request_swap is latest-wins — theirs
                    # can never apply); LATER entries belong to
                    # candidates still in flight and stay staged
                    del staged[: i + 1]
                    break
        eng = engine_cell.get("engine")
        if committed_info is not None and eng is not None:
            # /stats must describe the snapshot actually serving: swap in
            # the re-calibration info and restart the drift aggregate
            eng.update_quant_info(
                {k: v for k, v in committed_info.items()
                 if k != "weights_digest"}
            )

    def preparer(candidate_vars):
        """Hot-reload calibration stage: re-verify (digest) or re-derive
        scales for the CANDIDATE weights; calibrate.CalibrationError (or
        anything else) becomes a rejected:calibration rollback."""
        new_prepared, new_info = calibrate.calibrate_for_serving(
            model_q, model, candidate_vars,
            mode=mode,
            snapshot_path=args.path,
            vocab_size=vocab_size,
            pad_idx=pad_idx,
            bucket_edges=edges,
            batch_size=args.serve_batch_size,
            n_batches=args.calibration_batches,
        )
        new_prepared = jax.device_put(new_prepared)
        logger.info(
            f"QUANT-PATH {mode}: reload candidate re-calibrated "
            f"(scales {new_info['source']}, max |logit drift| "
            f"{new_info['max_abs_logit_drift']:.5f})"
        )
        telemetry.emit(
            "quant-path", event="reload-calibrated",
            **{k: v for k, v in new_info.items() if k != "weights_digest"},
        )
        with oracle_lock:
            oracle["staged"].append((
                new_prepared,
                jax.device_put(candidate_vars) if sampling
                else candidate_vars,
                new_info,
            ))
        return new_prepared

    def preparer_abort():
        """Probe rejected the candidate this preparer just staged: drop
        its pair (the most recent entry) so a rejected candidate neither
        leaks two device trees nor ever re-pairs the drift oracle."""
        with oracle_lock:
            if oracle["staged"]:
                oracle["staged"].pop()

    extras = {
        "precision": mode,
        "quant_info": {k: v for k, v in info.items()
                       if k != "weights_digest"},
        "drift_probe": drift_probe if args.quant_drift_sample > 0 else None,
        "drift_sample_every": args.quant_drift_sample,
        "swap_hook": swap_hook,
        "preparer": preparer,
        "preparer_abort": preparer_abort,
        "engine_cell": engine_cell,
    }
    return model_q, prepared, extras


def build_engine(args, model, variables, pad_idx, max_seq_len,
                 edges=None, precision="", quant_info=None,
                 drift_probe=None, drift_sample_every=0, swap_hook=None):
    from unicore_tpu.serve import ServeEngine, build_infer_fn

    if edges is None:
        edges = serve_buckets(args, max_seq_len)
    infer_fn, cache_probe = build_infer_fn(model)
    return ServeEngine(
        variables,
        infer_fn,
        bucket_edges=edges,
        batch_size=args.serve_batch_size,
        pad_idx=pad_idx,
        admission_capacity=args.admission_capacity,
        cache_size_probe=cache_probe,
        precision=precision,
        quant_info=quant_info,
        drift_probe=drift_probe,
        drift_sample_every=drift_sample_every,
        swap_hook=swap_hook,
    )


def start_fleet_registration(args, server, engine):
    """``--advertise``: self-register this replica through the fleet's
    serve-namespaced heartbeat lease plane (docs/serving.md 'Fleet').
    Raises on config/root trouble — the caller maps it to exit 78."""
    from unicore_tpu.serve import fleet

    if not getattr(args, "fleet_kv", None):
        raise ValueError(
            "--advertise requires --fleet-kv DIR (the coordination "
            "store the router reads membership from)"
        )
    client = fleet.open_fleet_kv(args.fleet_kv)
    name = args.replica_name or f"r{args.replica_index}"
    address = args.advertise
    if address == "auto":
        host = (
            args.host if args.host not in ("0.0.0.0", "::") else "127.0.0.1"
        )
        address = f"http://{host}:{server.server_address[1]}"
    from unicore_tpu.serve.fleet.router import host_port

    try:
        host_port(address)
    except (TypeError, ValueError):
        raise ValueError(
            f"--advertise {address!r} is not a routable address: the "
            "router dials it, so it must carry host:port (or use 'auto')"
        ) from None
    # the lease's snapshot digest tracks hot swaps: chain onto the
    # engine's swap hook (the quant CLI may already own one)
    digest_cell = {"d": fleet.model_digest(engine.variables)}
    prev_hook = engine._swap_hook

    def swap_hook(new_vars, tag):
        if prev_hook is not None:
            prev_hook(new_vars, tag)
        digest_cell["d"] = fleet.model_digest(new_vars)

    engine._swap_hook = swap_hook
    return fleet.ReplicaRegistrar(
        client, name, address,
        interval_s=args.fleet_interval,
        ready_fn=engine.ready,
        est_delay_fn=engine.queue.estimated_delay,
        digest_fn=lambda: digest_cell["d"],
        served_fn=lambda: engine.served,
    ).start()


def _start_flood_generator(args, engine, stop_event: threading.Event):
    """Synthetic traffic driver for the ``request-flood`` chaos kind:
    offers chaos.serve_flood_qps() requests per second straight into
    admission while the flood window is open.  Request lengths cycle the
    bucket set so the flood exercises every warmed program."""
    from unicore_tpu.distributed import chaos

    def run():
        i = 0
        while not stop_event.is_set():
            if not engine.ready():
                # don't open the flood window against a warming/reloading
                # server — the chaos proves admission control, not that a
                # cold server sheds everything
                stop_event.wait(timeout=0.1)
                continue
            qps = chaos.serve_flood_qps()
            if qps <= 0:
                stop_event.wait(timeout=0.1)
                continue
            edge = engine.bucket_edges[i % len(engine.bucket_edges)]
            length = max(1, edge - 1)
            engine.submit(
                [5] * length,
                args.default_deadline_ms / 1000.0,
                request_id=f"flood{i}",
            )
            i += 1
            stop_event.wait(timeout=1.0 / qps)

    t = threading.Thread(target=run, name="serve-flood", daemon=True)
    t.start()
    return t


def main(args) -> int:
    import jax  # noqa: F401  (backend init before any engine work)

    from unicore_tpu.checkpoint.emergency import Deadline, deadline_scope
    from unicore_tpu.distributed import chaos
    from unicore_tpu.platform_utils import (
        configure_compilation_cache,
        describe_devices,
    )
    from unicore_tpu.serve.http import bind_server

    configure_compilation_cache(
        getattr(args, "jax_compilation_cache_dir", None)
    )

    chaos.configure(args)
    # which fleet replica this process is — the @IDX target of the
    # replica-loss / replica-stall chaos kinds
    chaos.set_replica_index(getattr(args, "replica_index", 0) or 0)
    logger.info(args)
    logger.info("DEVICES " + json.dumps(describe_devices()))

    # serve-plane event journal (docs/observability.md): sheds, reload
    # outcomes, drains — default location is beside the served
    # checkpoint.  Fleet replicas journal under their replica index so N
    # replicas sharing one --telemetry-dir write N distinct files the
    # trace merger joins.
    from unicore_tpu import telemetry

    if not getattr(args, "telemetry_dir", None):
        args.telemetry_dir = os.path.join(
            os.path.dirname(os.path.abspath(args.path)) or ".", "telemetry"
        )
    telemetry.configure(
        args, rank=getattr(args, "replica_index", 0) or 0, role="serve"
    )

    # 1. verified model load (+ calibration when quantizing) -----------------
    try:
        model, variables, pad_idx, max_seq_len, vocab_size, eos_idx = \
            load_serving_model(args)
        preparer = preparer_abort = None
        if decode_serving_requested(args, model):
            # decode plane: paged KV cache + prefill/decode split +
            # step-level continuous batching (POST /v1/generate)
            engine = build_decode_engine(
                args, model, variables, pad_idx, max_seq_len,
                vocab_size, eos_idx,
            )
            logger.info(
                f"serving INCREMENTAL DECODE: cache buckets "
                f"{list(engine.bucket_edges)}, "
                f"{args.cache_pages} pages x {args.cache_page_size} rows, "
                f"kv {args.decode_kv}, decode batch "
                f"{args.decode_batch_size}, max_new {args.max_new_tokens}"
            )
        else:
            edges = serve_buckets(args, max_seq_len)
            quant_extras = {}
            serve_model, serve_variables = model, variables
            if args.serve_quantize != "off":
                serve_model, serve_variables, quant_extras = \
                    setup_quantized_serving(
                        args, model, variables, pad_idx, max_seq_len,
                        vocab_size, edges,
                    )
                preparer = quant_extras.pop("preparer")
                preparer_abort = quant_extras.pop("preparer_abort")
                engine_cell = quant_extras.pop("engine_cell")
            engine = build_engine(
                args, serve_model, serve_variables, pad_idx, max_seq_len,
                edges=edges, **quant_extras,
            )
            if preparer is not None:
                engine_cell["engine"] = engine
    except Exception as err:
        logger.error(
            f"FATAL: model load failed ({type(err).__name__}: {err}) — "
            f"exiting {EXIT_SERVE_MODEL_LOAD} "
            f"({SERVE_EXIT_CODE_NAMES[EXIT_SERVE_MODEL_LOAD]})",
            exc_info=True,
        )
        return EXIT_SERVE_MODEL_LOAD

    # 2. bind (probes live, readiness false) ---------------------------------
    try:
        server = bind_server(
            args.host, args.port, engine,
            read_timeout_s=args.request_read_timeout,
            default_deadline_ms=args.default_deadline_ms,
            max_deadline_ms=args.max_deadline_ms,
        )
    except OSError as err:
        logger.error(
            f"FATAL: cannot bind {args.host}:{args.port} ({err}) — exiting "
            f"{EXIT_SERVE_BIND} ({SERVE_EXIT_CODE_NAMES[EXIT_SERVE_BIND]})"
        )
        return EXIT_SERVE_BIND
    server.start()

    # fleet membership: self-register BEFORE warm-up so the router's
    # view shows the replica registered-but-not-ready while its bucket
    # programs compile (the lease carries readiness truthfully)
    registrar = None
    if getattr(args, "advertise", None):
        try:
            registrar = start_fleet_registration(args, server, engine)
        except Exception as err:
            logger.error(
                f"FATAL: fleet registration failed "
                f"({type(err).__name__}: {err}) — exiting "
                f"{EXIT_SERVE_FLEET_KV} "
                f"({SERVE_EXIT_CODE_NAMES[EXIT_SERVE_FLEET_KV]})"
            )
            server.shutdown()
            return EXIT_SERVE_FLEET_KV

    # 3. warm-up (readiness flips true inside) -------------------------------
    try:
        engine.warmup()
    except Exception as err:
        logger.error(
            f"FATAL: warm-up failed ({type(err).__name__}: {err}) — exiting "
            f"{EXIT_SERVE_MODEL_LOAD} "
            f"({SERVE_EXIT_CODE_NAMES[EXIT_SERVE_MODEL_LOAD]})",
            exc_info=True,
        )
        if registrar is not None:
            registrar.stop(goodbye=True)
        server.shutdown()
        return EXIT_SERVE_MODEL_LOAD
    if registrar is not None:
        registrar.publish_now()  # readiness flipped: don't wait the beat

    # 4. serve ---------------------------------------------------------------
    engine.start()

    hot_reloader = None
    if args.reload_interval > 0 or registrar is not None:
        from unicore_tpu import checkpoint_utils
        from unicore_tpu.serve import HotReloader

        hot_reloader = HotReloader(
            engine, checkpoint_utils.load_checkpoint_to_cpu,
            # quantized serving: candidates re-verify/re-derive scales
            # (rejected:calibration on failure) and the structure
            # check runs against the fp32 tree — the engine's live
            # tree is the PREPARED one
            preparer=preparer,
            preparer_abort=preparer_abort,
            structure_ref=variables if preparer is not None else None,
        )
    reload_runner = None
    if args.reload_interval > 0:
        from unicore_tpu.serve import CheckpointWatcher, ReloadRunner

        reload_runner = ReloadRunner(
            CheckpointWatcher(args.path), hot_reloader,
            args.reload_interval,
        )
        reload_runner.start()
    if registrar is not None:
        # the router's ROLLING reload drives this replica's own
        # verify→probe→swap through POST /v1/reload (always on the
        # replica's OWN --path; the router cannot point it elsewhere)
        server.reloader = hot_reloader
        server.reload_path = args.path

    flood_stop = threading.Event()
    flood_thread = _start_flood_generator(args, engine, flood_stop)

    started = time.monotonic()
    while not _drain_requested.is_set():
        if not engine.healthy():
            # the engine loop died (XLA error, device loss): a process
            # that can never serve another request must exit for its
            # supervisor, not linger as a zombie with liveness green
            logger.error(
                f"FATAL: serve engine loop died "
                f"({type(engine.fatal_error).__name__ if engine.fatal_error else 'thread exit'}: "
                f"{engine.fatal_error}) — exiting 1"
            )
            flood_stop.set()
            if reload_runner is not None:
                reload_runner.stop()
            if registrar is not None:
                # deregister (goodbye) rather than rot: the router drops
                # this replica NOW instead of waiting a loss verdict
                registrar.stop(goodbye=True)
            server.shutdown()
            return 1
        if (
            args.serve_max_seconds > 0
            and time.monotonic() - started >= args.serve_max_seconds
        ):
            logger.info(
                f"--serve-max-seconds ({args.serve_max_seconds:g}s) "
                "reached: starting the graceful drain"
            )
            break
        _drain_requested.wait(timeout=0.2)

    # 5. drain ---------------------------------------------------------------
    # reload/flood planes stop FIRST: a reload landing mid-drain would
    # race the readiness state (the engine also refuses to resurrect a
    # draining server — belt and suspenders), and a flood would fight the
    # flush for the drain budget
    flood_stop.set()
    if reload_runner is not None:
        reload_runner.stop()
    if registrar is not None:
        # drain/router handshake: flip the lease ready=false BEFORE the
        # flush, so the router stops routing here within one beat (its
        # data path also reacts to the first 503 immediately)
        from unicore_tpu.serve.engine import PHASE_DRAINING

        engine.set_ready(False, PHASE_DRAINING)
        registrar.publish_now()
    deadline = Deadline(args.drain_deadline)
    with deadline_scope(deadline):
        drained = engine.drain(deadline)
    if registrar is not None:
        # clean exit says goodbye: the router DEREGISTERS this replica
        # (no loss verdict) instead of expiring its lease
        registrar.stop(goodbye=True)
    server.shutdown()
    flood_thread.join(timeout=2.0)
    logger.info(f"final serve stats: {engine.stats()}")
    if not drained:
        logger.error(
            f"exiting {EXIT_SERVE_DRAIN_DEADLINE} "
            f"({SERVE_EXIT_CODE_NAMES[EXIT_SERVE_DRAIN_DEADLINE]})"
        )
        return EXIT_SERVE_DRAIN_DEADLINE
    logger.info("serve shutdown clean: drained in-flight work, exiting 0")
    return EXIT_OK


def cli_main() -> None:
    # same env contract as the training CLI: UNICORE_TPU_PLATFORM=cpu
    # forces the virtual-CPU mesh before any jax backend init
    from unicore_tpu.platform_utils import force_host_cpu_from_env

    force_host_cpu_from_env(default_devices=1)

    from unicore_tpu import options

    parser = options.get_serving_parser()
    args = parser.parse_args()

    try:
        signal.signal(signal.SIGTERM, _handle_signal)
        signal.signal(signal.SIGINT, _handle_signal)
    except ValueError:
        logger.warning(
            "could not install signal handlers (not the main thread); "
            "graceful drain is unavailable"
        )

    sys.exit(main(args))


if __name__ == "__main__":
    cli_main()
