"""Cascade serving entry point: small + large model, Gatekeeper deferral.

--widths picks the model ladder: reduced (default; 2-layer CPU-scale
variant of --arch) or published (the --arch config as published, params
in bf16 — what a TPU serves; see chip_smoke.py).

Two engines (see repro.serving):
  * static      — lock-step batches, full max_new decode before deferral
  * continuous  — continuous batching with in-flight deferral once the
                  running mean confidence drops below tau - margin
                  (saves the remaining M_S steps), over one of two KV
                  backends: --backend slot (dense worst-case rows) or
                  --backend paged (block-paged cache, ragged prompts,
                  chunked prefill batched across same-offset requests;
                  size the budget with --blocks, pick the Pallas paged
                  flash-decode kernel with --paged-kernel)

Deferred requests regenerate on a pluggable M_L backend
(--large-backend): sync runs M_L inline on the decode loop (reference);
thread runs it on a worker thread so M_S decode never stalls on large
batches; stub adds a serialized request/response pipe with injectable
latency (--stub-latency), the shape of a real RPC. --large-batch sets
the regeneration batch size and --large-max-wait bounds how long a
partial batch may wait before flushing.

The distributed tier (docs/serving.md): --large-backend socket talks to
one M_L server process (start it with `python -m repro.launch.ml_server`,
point at it with --ml-address host:port), --large-backend pool
load-balances across several (--ml-address host:a,host:b,...) with
health checks, dead-replica ejection, and in-flight re-dispatch.
--ml-spawn N starts N in-process demo servers instead of requiring
separate processes; --ml-request-timeout/--ml-connect-timeout/
--ml-retries/--ml-health-interval tune the failure handling. With
socket/pool the batching policy (--large-batch/--large-max-wait) is
applied server-side — spawned servers inherit this process's flags,
external servers use their own.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --requests 32 --max-new 8 --deferral-ratio 0.3 \
        --engine continuous --slots 8 --arrival-rate 50 \
        --audit-log /tmp/serve_audit.jsonl

    # ragged prompts over the paged backend
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --backend paged --ragged-min 8 --ragged-max 32 --block-size 8 \
        --prefill-chunk 8

The cascade ladder (docs/serving.md): --tiers N serves an N-tier
cascade (intermediate demo models interpolated between M_S and M_L),
each adjacent pair gated by its own calibrated deferral edge; --signal
picks the per-edge deferral signal (eq.-8 mean confidence, or k-sample
semantic-agreement voting with --signal-k/--signal-temperature);
--recalibrate turns on the online tau controller (EWMA deferral-ratio
tracker with hysteresis) that nudges every edge's tau toward
--recalib-target under arrival drift. Contradictory flag combinations
(e.g. --ml-address with --large-backend sync, paged knobs with
--backend slot) are rejected at argparse time instead of silently
ignored.

Pressure & overload (docs/serving.md): --oversubscribe F admits paged
requests against a virtual budget of round(blocks * F); when the
physical pool runs dry mid-flight, --pressure-policy picks the victim
handling — preempt (save state, requeue age-first, bit-exact resume;
bounded by --max-preemptions before escalating to defer), defer
(straight up the cascade ladder, deferred_reason="oom"), or shed
(REJECTED). --swap-blocks N spills cold registered prefix blocks to a
host-RAM LRU tier instead of dropping them. Admission overload control:
--max-queue bounds the ready queue (overflow shed newest-first as
REJECTED) and --deadline-ms sheds requests still queued past their
deadline as EXPIRED.

Observability (continuous engine; see docs/observability.md):
--trace-out dumps a Perfetto-loadable Chrome trace of the run,
--metrics-out / --metrics-port export the Prometheus metrics registry
(file dump / live scrape endpoint), --device-timing splits host vs
device wall time per phase, and --profile-dir captures a jax.profiler
window of the first --profile-iters engine iterations.
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config, reduced
from repro.core.deferral import SemanticAgreementSignal
from repro.data.synthetic import make_lm_stream, make_ragged_lm_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.serving import (CascadeEngine, CascadeSpec, CascadeTier,
                           ContinuousCascadeEngine, DeferralEdge,
                           EngineConfig, MLBackendConfig, ModelRunner,
                           PagedConfig, PressureConfig, RecalibConfig,
                           make_requests, poisson_arrivals)
from repro.serving.obs import (Observability, add_obs_args,
                               obs_config_from_args)


WIDTHS = ("reduced", "published")


def build_runners(arch: str, seed: int, widths: str = "reduced"):
    small, large = build_ladder(arch, seed, 2, widths)
    return small, large, small.cfg


def build_ladder(arch: str, seed: int, n_tiers: int,
                 widths: str = "reduced"):
    """One ModelRunner per tier, capacity interpolated from `arch` (tier
    0) up to the "large" config derived from it (last tier: 4 layers,
    twice the width and FFN, 8 heads): intermediate tiers grow depth/FFN
    only, keeping d_model/head count. `widths="reduced"` starts from the
    2-layer CPU-scale variant, so a CPU demo of a 3- or 4-tier ladder
    stays fast; `"published"` starts from the config as published, with
    bf16 params."""
    key = jax.random.PRNGKey(seed)
    if widths == "reduced":
        small_cfg = reduced(get_config(arch))
    elif widths == "published":
        small_cfg = get_config(arch).replace(param_dtype="bfloat16")
    else:
        raise ValueError(f"widths must be one of {WIDTHS}, got {widths!r}")
    large_cfg = small_cfg.replace(name=small_cfg.name + "-large",
                                  n_layers=4, d_model=small_cfg.d_model * 2,
                                  n_heads=8, d_ff=small_cfg.d_ff * 2)
    cfgs = [small_cfg]
    for i in range(1, n_tiers - 1):
        f = i / (n_tiers - 1)
        cfgs.append(small_cfg.replace(
            name=f"{small_cfg.name}-mid{i}",
            n_layers=round(small_cfg.n_layers
                           + f * (large_cfg.n_layers - small_cfg.n_layers)),
            d_ff=round(small_cfg.d_ff * (1 + f))))
    cfgs.append(large_cfg)
    # tier 0 keeps the base key (the historical two-runner init), so a
    # 2-tier ladder is weight-identical to every earlier bench run
    return [ModelRunner(c, tfm.init_params(
                c, key if i == 0 else jax.random.fold_in(key, i)))
            for i, c in enumerate(cfgs)]


def make_remote_factory(kind: str, addresses, *, connect_timeout: float,
                        request_timeout: float, retries: int,
                        health_interval: float):
    """Callable `large_backend` for the engine: builds the socket/pool
    backend with the engine-supplied context (max_new, metrics registry)
    plus the addresses/timeouts closed over here. The runner argument is
    ignored — with a remote tier, M_L compute lives in the server."""
    from repro.serving.remote import ReplicaPool, SocketBackend

    def factory(runner=None, max_new=0, large_batch=None, max_wait=None,
                stub_latency=0.0, registry=None):
        if kind == "socket":
            return SocketBackend(addresses[0],
                                 connect_timeout=connect_timeout,
                                 request_timeout=request_timeout,
                                 retries=retries, registry=registry)
        return ReplicaPool(addresses,
                           connect_timeout=connect_timeout,
                           request_timeout=request_timeout,
                           retries=retries,
                           health_interval=health_interval,
                           max_new=max_new, large_batch=large_batch,
                           registry=registry)

    return factory


def main(argv=None):
    """Parse `argv`, build the ladder, serve; returns (engine, result)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--widths", choices=WIDTHS, default="reduced",
                    help="reduced: 2-layer CPU-scale variant of --arch; "
                         "published: --arch as published, bf16 params")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--deferral-ratio", type=float, default=0.3)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--min-tokens", type=int, default=2)
    ap.add_argument("--margin", type=float, default=0.0)
    ap.add_argument("--no-early-exit", action="store_true")
    ap.add_argument("--tiers", type=int, default=2,
                    help="cascade ladder depth (continuous engine): 2 = "
                         "the paper's M_S/M_L pair; >2 inserts "
                         "intermediate tiers, each with its own "
                         "calibrated deferral edge")
    ap.add_argument("--signal",
                    choices=("mean_confidence", "semantic_agreement"),
                    default="mean_confidence",
                    help="per-edge deferral signal: eq.-8 mean negative "
                         "entropy (running form, supports in-flight "
                         "early exit) or k-sample semantic-agreement "
                         "voting (finalize-only)")
    ap.add_argument("--signal-k", type=int, default=4,
                    help="semantic_agreement: samples per vote")
    ap.add_argument("--signal-temperature", type=float, default=0.8,
                    help="semantic_agreement: sampling temperature")
    ap.add_argument("--recalibrate", action="store_true",
                    help="continuous engine: recalibrate each edge's tau "
                         "online (EWMA quantile tracker with hysteresis) "
                         "toward --recalib-target under arrival drift")
    ap.add_argument("--recalib-target", type=float, default=-1.0,
                    help="target deferral ratio the online controller "
                         "holds per edge (default: --deferral-ratio)")
    ap.add_argument("--recalib-step", type=float, default=0.08,
                    help="recalibration: Robbins-Monro step scale")
    ap.add_argument("--recalib-deadband", type=float, default=0.1,
                    help="recalibration: hysteresis deadband — the "
                         "controller stays idle until |ewma - target| "
                         "exceeds this")
    ap.add_argument("--recalib-warmup", type=int, default=32,
                    help="recalibration: observations before the "
                         "controller may move tau")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals/s; 0 = all at t=0")
    ap.add_argument("--large-backend",
                    choices=("sync", "thread", "stub", "socket", "pool"),
                    default="sync",
                    help="M_L regeneration backend: inline (sync), "
                         "worker thread overlapped with M_S decode "
                         "(thread), serialized RPC stub (stub), one "
                         "remote M_L server (socket), or N replicas with "
                         "health checks + re-dispatch (pool)")
    ap.add_argument("--ml-address", default="",
                    help="socket/pool: comma-separated host:port of "
                         "running M_L servers (repro.launch.ml_server); "
                         "empty with --ml-spawn starts in-process demo "
                         "servers")
    ap.add_argument("--ml-spawn", type=int, default=0,
                    help="socket/pool: start this many in-process M_L "
                         "servers on ephemeral ports instead of "
                         "connecting to --ml-address")
    ap.add_argument("--ml-connect-timeout", type=float, default=2.0)
    ap.add_argument("--ml-request-timeout", type=float, default=30.0)
    ap.add_argument("--ml-retries", type=int, default=3,
                    help="socket/pool: RPC retry attempts "
                         "(exponential backoff) before giving up")
    ap.add_argument("--ml-health-interval", type=float, default=2.0,
                    help="pool: seconds between replica health probes")
    ap.add_argument("--large-batch", type=int, default=0,
                    help="M_L regeneration batch size (0 = one "
                         "exact-size batch at end of run)")
    ap.add_argument("--large-max-wait", type=float, default=0.0,
                    help="seconds a partial M_L batch may wait before "
                         "flushing padded (0 = wait for a full batch)")
    ap.add_argument("--stub-latency", type=float, default=0.0,
                    help="injected per-batch RPC latency for "
                         "--large-backend stub")
    ap.add_argument("--audit-log", default=None,
                    help="JSONL audit log path (continuous engine)")
    ap.add_argument("--backend", choices=("slot", "paged"), default="slot",
                    help="continuous engine KV-cache backend")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged backend: tokens per cache block")
    ap.add_argument("--blocks", type=int, default=0,
                    help="paged backend: physical block budget "
                         "(0 = worst case, always fits)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged backend: prefill chunk tokens "
                         "(0 = whole prompt in one chunk)")
    ap.add_argument("--paged-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="paged backend: route decode through the Pallas "
                         "paged flash-decode kernels (auto = on for TPU, "
                         "XLA gather fallback on CPU)")
    ap.add_argument("--serial-prefill", action="store_true",
                    help="paged backend: disable batched same-offset "
                         "prefill chunk dispatch (debug/parity)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="paged backend: disable copy-on-write prefix "
                         "sharing (every request prefills and maps its "
                         "whole prompt even when the blocks are already "
                         "resident)")
    ap.add_argument("--oversubscribe", type=float, default=1.0,
                    help="paged backend: admit against a virtual budget "
                         "of round(blocks * factor); > 1.0 allows block "
                         "pressure, handled by --pressure-policy "
                         "(1.0 = classic reservation invariant)")
    ap.add_argument("--pressure-policy",
                    choices=("preempt", "defer", "shed"),
                    default="preempt",
                    help="paged backend under --oversubscribe > 1: evict "
                         "the youngest running request by preempt-and-"
                         "requeue (bit-exact resume), defer-on-OOM up "
                         "the cascade ladder, or shed (REJECTED)")
    ap.add_argument("--max-preemptions", type=int, default=2,
                    help="preempt policy: preemption bound per request "
                         "before escalating to defer-on-OOM")
    ap.add_argument("--swap-blocks", type=int, default=0,
                    help="paged backend: host-RAM swap-tier capacity in "
                         "blocks for cold registered prefix blocks "
                         "(0 = evicted cold blocks are dropped)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission overload control: bound on the ready "
                         "arrival queue; overflow is shed newest-first "
                         "as REJECTED (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request queueing deadline in ms from "
                         "arrival; requests still queued past it are "
                         "shed as EXPIRED (0 = no deadlines)")
    ap.add_argument("--ragged-min", type=int, default=0,
                    help=">0: ragged prompt lengths uniform in "
                         "[ragged-min, ragged-max] (continuous engine)")
    ap.add_argument("--ragged-max", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    add_obs_args(ap)
    args = ap.parse_args(argv)

    def given(dest: str) -> bool:
        return getattr(args, dest) != ap.get_default(dest)

    if args.ragged_min > 0 and args.engine == "static":
        ap.error("--ragged-min/--ragged-max need --engine continuous "
                 "(the static engine serves lock-step uniform batches)")
    obs_cfg = obs_config_from_args(args)
    if args.engine == "static" and obs_cfg.any_enabled:
        ap.error("observability flags (--trace-out/--metrics-*/"
                 "--device-timing/--profile-dir) need --engine continuous")

    # reject contradictory flag combinations up front: a tuning flag that
    # the selected backend/engine would silently ignore is a user error,
    # not a no-op
    remote = args.large_backend in ("socket", "pool")
    if not remote:
        for dest in ("ml_address", "ml_spawn", "ml_connect_timeout",
                     "ml_request_timeout", "ml_retries",
                     "ml_health_interval"):
            if given(dest):
                ap.error(f"--{dest.replace('_', '-')} needs "
                         f"--large-backend socket|pool (got "
                         f"--large-backend {args.large_backend}, which "
                         f"would silently ignore it)")
    if args.large_backend != "stub" and given("stub_latency"):
        ap.error(f"--stub-latency needs --large-backend stub (got "
                 f"--large-backend {args.large_backend}, which would "
                 f"silently ignore it)")
    if args.backend != "paged":
        for dest in ("block_size", "blocks", "prefill_chunk",
                     "paged_kernel", "serial_prefill",
                     "no_prefix_sharing", "oversubscribe",
                     "pressure_policy", "max_preemptions", "swap_blocks"):
            if given(dest):
                ap.error(f"--{dest.replace('_', '-')} needs --backend "
                         f"paged (got --backend {args.backend}, which "
                         f"would silently ignore it)")
    if args.oversubscribe < 1.0:
        ap.error(f"--oversubscribe must be >= 1.0 (1.0 = reservation-"
                 f"only), got {args.oversubscribe}")
    if args.oversubscribe == 1.0:
        # pressure can only fire past the reservation invariant: tuning
        # its handling without enabling it is a silent no-op
        for dest in ("pressure_policy", "max_preemptions"):
            if given(dest):
                ap.error(f"--{dest.replace('_', '-')} needs "
                         f"--oversubscribe > 1.0 (reservation-only "
                         f"admission never hits block pressure)")
    if args.pressure_policy != "preempt" and given("max_preemptions"):
        ap.error(f"--max-preemptions needs --pressure-policy preempt "
                 f"(got --pressure-policy {args.pressure_policy}, which "
                 f"never preempts)")
    if args.oversubscribe > 1.0 and not given("blocks"):
        ap.error("--oversubscribe > 1.0 needs an explicit --blocks "
                 "budget (the worst-case default never runs out)")
    if args.max_queue < 0:
        ap.error(f"--max-queue must be >= 0, got {args.max_queue}")
    if args.deadline_ms < 0:
        ap.error(f"--deadline-ms must be >= 0, got {args.deadline_ms}")
    if not args.recalibrate:
        for dest in ("recalib_target", "recalib_step",
                     "recalib_deadband", "recalib_warmup"):
            if given(dest):
                ap.error(f"--{dest.replace('_', '-')} needs "
                         f"--recalibrate")
    if args.tiers < 2:
        ap.error(f"--tiers must be >= 2, got {args.tiers}")
    if args.engine == "static":
        for dest, flag in (("tiers", "--tiers"), ("signal", "--signal"),
                           ("recalibrate", "--recalibrate"),
                           ("max_queue", "--max-queue"),
                           ("deadline_ms", "--deadline-ms")):
            if given(dest):
                ap.error(f"{flag} needs --engine continuous")
    if args.signal != "semantic_agreement":
        for dest in ("signal_k", "signal_temperature"):
            if given(dest):
                ap.error(f"--{dest.replace('_', '-')} needs "
                         f"--signal semantic_agreement")
    if remote:
        if args.tiers != 2:
            ap.error("--large-backend socket|pool drives the final "
                     "(remote) tier of a 2-tier cascade; --tiers > 2 "
                     "needs a local backend per intermediate tier")
        if args.ml_spawn <= 0 and not args.ml_address:
            ap.error("--large-backend socket/pool needs --ml-address "
                     "host:port[,host:port...] or --ml-spawn N")
        if (args.large_backend == "socket" and args.ml_address
                and len(args.ml_address.split(",")) != 1):
            ap.error("--large-backend socket takes exactly one "
                     "--ml-address; use --large-backend pool for several")

    enable_compile_cache()
    key = jax.random.PRNGKey(args.seed)
    runners = build_ladder(args.arch, args.seed, args.tiers, args.widths)
    for i, r in enumerate(runners):
        leaves = jax.tree.leaves(r.params)
        print(f"tier {i}: {r.cfg.name} ({args.widths} widths, "
              f"{r.cfg.n_layers} layers, d_model {r.cfg.d_model}, vocab "
              f"{r.cfg.vocab_size}): "
              f"{sum(x.size for x in leaves) / 1e9:.3f} B params, "
              f"{sum(x.nbytes for x in leaves) / 1e9:.3f} GB "
              f"{r.cfg.param_dtype}")
    small, large = runners[0], runners[-1]
    small_cfg = small.cfg

    ragged = args.ragged_min > 0
    cal_len = ((args.ragged_min + max(args.ragged_max, args.ragged_min))
               // 2 if ragged else args.prompt_len)
    cal = make_lm_stream(jax.random.fold_in(key, 1), args.requests,
                         cal_len, small_cfg.vocab_size)
    if ragged:
        live = make_ragged_lm_stream(
            jax.random.fold_in(key, 2), args.requests, args.ragged_min,
            max(args.ragged_max, args.ragged_min), small_cfg.vocab_size)
    else:
        live = make_lm_stream(jax.random.fold_in(key, 2), args.requests,
                              args.prompt_len, small_cfg.vocab_size)

    if args.engine == "static":
        engine = CascadeEngine(small, large)
        tau = engine.calibrate(cal, args.prompt_len, args.max_new,
                               args.deferral_ratio)
        print(f"calibrated tau={tau:.4f} for target deferral "
              f"{args.deferral_ratio}")
        res = engine.serve(live, args.prompt_len, args.max_new)
        print(f"served {len(live)} requests: deferral_ratio="
              f"{res.deferral_ratio:.3f}, compute_cost={res.compute_cost:.3f}x,"
              f" mean_confidence={res.confidence.mean():.4f}")
        print("first tokens:", res.tokens[:4].tolist())
        return engine, res

    ml_servers = []
    large_backend = args.large_backend
    if args.large_backend in ("socket", "pool"):
        if args.ml_spawn > 0:
            from repro.serving.remote import MLServer
            n = max(args.ml_spawn,
                    2 if args.large_backend == "pool" else 1)
            for _ in range(n):
                ml_servers.append(MLServer(
                    large, max_new=args.max_new,
                    large_batch=args.large_batch or None,
                    max_wait=args.large_max_wait or None).start())
            addresses = [s.address for s in ml_servers]
            print(f"spawned {n} in-process M_L server(s): "
                  + ", ".join(f"{h}:{p}" for h, p in addresses))
        elif args.ml_address:
            addresses = args.ml_address.split(",")
        else:
            ap.error("--large-backend socket/pool needs --ml-address "
                     "host:port[,host:port...] or --ml-spawn N")
        if args.large_backend == "socket" and len(addresses) != 1:
            ap.error("--large-backend socket takes exactly one "
                     "--ml-address; use --large-backend pool for several")
        large_backend = make_remote_factory(
            args.large_backend, addresses,
            connect_timeout=args.ml_connect_timeout,
            request_timeout=args.ml_request_timeout,
            retries=args.ml_retries,
            health_interval=args.ml_health_interval)

    # declarative ladder: one tier per runner, cost interpolated
    # geometrically from the paper's M_S (0.2) to M_L (1.0) units; one
    # deferral edge per adjacent pair, all carrying the same signal
    n = len(runners)
    costs = [0.2 * (1.0 / 0.2) ** (i / (n - 1)) for i in range(n)]
    tiers = [CascadeTier(r.cfg.name, runner=r, cost=costs[i])
             for i, r in enumerate(runners)]
    if callable(large_backend):          # socket/pool factory (2-tier)
        tiers[-1] = CascadeTier(tiers[-1].name, runner=large,
                                cost=costs[-1], backend=large_backend)

    def make_signal():
        if args.signal == "semantic_agreement":
            return SemanticAgreementSignal(k=args.signal_k,
                                           temperature=args.signal_temperature,
                                           seed=args.seed)
        return "mean_confidence"

    spec = CascadeSpec(
        tiers=tiers,
        edges=[DeferralEdge(signal=make_signal(), margin=args.margin,
                            min_tokens=args.min_tokens)
               for _ in range(n - 1)])
    recalib_target = (args.recalib_target if args.recalib_target >= 0
                      else args.deferral_ratio)
    pressure = (PressureConfig(oversubscribe=args.oversubscribe,
                               policy=args.pressure_policy,
                               max_preemptions=args.max_preemptions,
                               swap_blocks=args.swap_blocks)
                if args.oversubscribe > 1.0 or args.swap_blocks > 0
                else None)
    config = EngineConfig(
        n_slots=args.slots, early_exit=not args.no_early_exit,
        backend=args.backend,
        max_queue=args.max_queue or None,
        deadline_s=args.deadline_ms / 1e3 if args.deadline_ms else None,
        paged=PagedConfig(
            block_size=args.block_size,
            n_blocks=args.blocks or None,
            prefill_chunk=args.prefill_chunk or None,
            paged_kernel={"auto": None, "on": True,
                          "off": False}[args.paged_kernel],
            batch_prefill=not args.serial_prefill,
            prefix_sharing=not args.no_prefix_sharing,
            pressure=pressure),
        ml=MLBackendConfig(
            kind=args.large_backend if not callable(large_backend)
            else "sync",
            large_batch=args.large_batch or None,
            max_wait=args.large_max_wait or None,
            stub_latency=args.stub_latency),
        recalibration=(RecalibConfig(step=args.recalib_step,
                                     deadband=args.recalib_deadband,
                                     warmup=args.recalib_warmup)
                       if args.recalibrate else None),
        recalib_target=recalib_target)
    engine = ContinuousCascadeEngine(spec, config)
    taus = engine.calibrate(cal, cal_len, args.max_new,
                            args.deferral_ratio)
    taus = taus if isinstance(taus, list) else [taus]
    print(f"calibrated tau(s) "
          f"{', '.join(f'{t:.4f}' for t in taus)} for target deferral "
          f"{args.deferral_ratio} per edge"
          + (" (online recalibration on)" if args.recalibrate else ""))
    arrivals = (poisson_arrivals(len(live), args.arrival_rate, args.seed)
                if args.arrival_rate > 0 else None)
    reqs = make_requests(live, args.max_new, arrivals)
    # caller-owned observability runtime: the /metrics endpoint stays up
    # (and announced) before the run starts and until after the final
    # scrape is dumped
    obs = Observability(obs_cfg)
    server = obs.start_server()
    if server is not None:
        print(f"metrics endpoint: {server.url}")
    try:
        res = engine.run(reqs, args.max_new, audit_path=args.audit_log,
                         obs=obs)
    finally:
        obs.finish()
        for srv in ml_servers:
            srv.stop()
    print(f"served {len(live)} requests on {args.slots} slots "
          f"({args.backend} backend, {args.tiers}-tier ladder, upper "
          f"tiers via {args.large_backend}) in "
          f"{res.steps} M_S steps: deferral_ratio={res.deferral_ratio:.3f}, "
          f"early_exits={int(res.early_exited.sum())}, "
          f"saved_M_S_steps={res.saved_steps}")
    if args.tiers > 2:
        print(f"tier_served={res.stats['tier_served']} over tiers "
              f"{res.stats['tier_names']}, per-edge deferrals "
              f"{res.stats['edge_deferrals']}")
    if pressure is not None or args.max_queue or args.deadline_ms:
        st = res.stats
        print(f"pressure/overload: preemptions={st['n_preemptions']}, "
              f"oom_deferrals={st['oom_deferrals']}, "
              f"rejected={st['n_rejected']}, expired={st['n_expired']}, "
              f"swap_outs={st.get('swap_outs', 0)}, "
              f"swap_ins={st.get('swap_ins', 0)}")
    if args.recalibrate:
        rc = res.stats["recalibration"]
        drift = [f"{a:.4f}->{b:.4f}"
                 for a, b in zip(taus, rc["tau_final"])]
        print(f"online recalibration: tau drift {', '.join(drift)} "
              f"({rc['tau_updates']} updates, ewma deferral "
              f"{rc['ewma_ratio']})")
    print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in res.stats.items()}, indent=1))
    if args.audit_log:
        print(f"audit log written to {args.audit_log}")
    if args.trace_out:
        print(f"trace written to {args.trace_out} "
              f"(load in https://ui.perfetto.dev)")
    if args.metrics_out:
        print(f"metrics scrape written to {args.metrics_out}")
    if args.profile_dir:
        print(f"jax.profiler trace in {args.profile_dir}")
    print("first tokens:", res.tokens[:4].tolist())
    return engine, res


if __name__ == "__main__":
    main()
