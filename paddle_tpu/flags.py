"""Global flag registry.

TPU-native analogue of the reference's gflags system
(/root/reference/paddle/fluid/platform/flags.cc, exposed to Python via
pybind.cc:1484 `init_gflags` and `fluid.set_flags`).  Flags are plain Python
state: declared with `declare_flag`, overridable from the environment via
``FLAGS_<name>`` at import time, and settable at runtime with
:func:`set_flags` / readable with :func:`get_flags`.

Unlike the reference there is no C++ side to mirror into -- XLA owns device
memory and stream management -- so only behavior-relevant flags survive the
translation (numeric checking, allocator hints forwarded to XLA, executor
debug modes).
"""

import os
import tempfile

_REGISTRY = {}


class _Flag:
    __slots__ = ("name", "default", "value", "type", "help")

    def __init__(self, name, default, help_str):
        self.name = name
        self.default = default
        self.value = default
        self.type = type(default)
        self.help = help_str


def _coerce(flag, value):
    if flag.type is bool:
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    return flag.type(value)


def declare_flag(name, default, help_str=""):
    """Declare a global flag. Env var ``FLAGS_<name>`` overrides the default."""
    flag = _Flag(name, default, help_str)
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        flag.value = _coerce(flag, env)
    _REGISTRY[name] = flag
    return flag


def set_flags(flags_dict):
    """Set flags at runtime. Parity: ``fluid.set_flags``."""
    for name, value in flags_dict.items():
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag: {name}")
        flag = _REGISTRY[key]
        flag.value = _coerce(flag, value)


def get_flags(names):
    """Read current flag values. Accepts a name or list of names."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for name in names:
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag: {name}")
        out["FLAGS_" + key] = _REGISTRY[key].value
    return out


def flag(name):
    """Fast internal accessor for a single flag value."""
    return _REGISTRY[name].value


def all_flags():
    return {f.name: f.value for f in _REGISTRY.values()}


# ---------------------------------------------------------------------------
# Core flags (subset of platform/flags.cc with TPU-meaningful semantics)
# ---------------------------------------------------------------------------

# Numeric sanitizer: check every op output for NaN/Inf
# (parity: FLAGS_check_nan_inf, platform/flags.cc:44 + operator.cc:1032).
declare_flag("check_nan_inf", False, "Check every op output for NaN/Inf.")

# Run programs op-by-op eagerly instead of jit-compiling the whole step.
# Debug analogue of the reference's single-threaded Executor hot loop.
declare_flag("eager_executor", False, "Interpret programs without jit (debug).")

# Seed for parameter init when program/seed not set.
declare_flag("global_seed", 0, "Fallback RNG seed for initializers.")

# Print op types as they execute (VLOG-style tracing).
declare_flag("executor_log_ops", False, "Log each op executed.")

# AMP default dtype for TPU ("bfloat16" is the native choice; "float16"
# for parity with the reference's fp16 AMP lists).
declare_flag("amp_dtype", "bfloat16", "Low-precision dtype used by AMP.")

# Profiler output directory (under TMPDIR, so two checkouts run with
# their own TMPDIR keep their traces apart).
declare_flag("profiler_dir",
             os.path.join(tempfile.gettempdir(), "paddle_tpu_profile"),
             "Profiler trace dir.")

declare_flag("use_pallas_layer_norm", False,
             "Route last-axis layer_norm through the Pallas fused kernel "
             "on TPU (D % 128 == 0).")

declare_flag("use_pallas_dgc_topk", False,
             "Route DGC top-k gradient selection through the streaming "
             "Pallas histogram-threshold kernel instead of lax.top_k "
             "(approximate: keeps >= k elements).")

# Default jax matmul/conv precision for compiled train/eval steps
# ("" = jax's own default).  "bfloat16" pins conv+matmul inputs to the
# bf16 MXU path (the explicit precision lever of the ResNet-50 A/B
# grid); "highest"/"float32" forces full-precision accumulating passes
# for numerics-sensitive runs.  Read by models/train.make_train_step
# (precision=None) and framework/compiler.apply_precision_policy.
declare_flag("conv_matmul_precision", "",
             "Default matmul/conv precision for compiled steps "
             "('', 'bfloat16', 'tensorfloat32', 'float32', 'highest').")

# Always-on flight recorder (monitor/flight_recorder.py): a bounded
# ring of recent step records, compile events and recovery events that
# costs one deque append per step while healthy and writes a
# post-mortem JSONL + chrome trace on crash / unhandled exception /
# anomaly-guard escalation.  FLAGS_flight_recorder=0 disables all of
# it (recording AND dumps).
declare_flag("flight_recorder", True,
             "Keep the always-on post-mortem ring buffer recording.")
declare_flag("flight_recorder_steps", 256,
             "How many recent step records the flight recorder keeps.")
declare_flag("flight_recorder_dir",
             os.path.join(tempfile.gettempdir(), "paddle_tpu_flight"),
             "Directory flight-recorder post-mortem dumps land in.")

# Static Program verifier (paddle_tpu.analysis): lint every program
# BEFORE tracing/compiling — shape/dtype inference, use-before-def,
# dead code, donation hazards, distributed misconfigurations — with
# results cached per (program, _version) so the steady-state dispatch
# fast path pays one flag read.  "off" (default) skips the verifier
# entirely; "warn" emits a ProgramLintWarning once per program
# version; "error" raises ProgramLintError pre-trace when any PT1xx
# error is found (the strongest fail-fast of the resilience taxonomy:
# INVALID_ARGUMENT-class failures never reach the compiler).
declare_flag("static_check", "off",
             "Static program verification before tracing: "
             "off | warn | error.")

# Static sharding analyzer (paddle_tpu.analysis.sharding, ISSUE 12):
# a parameter left replicated by the partition rules above this many
# bytes lints as PT302 — the "forgot to shard the embedding" OOM,
# caught before any trace.  0 disables the check.
declare_flag("replicated_param_bytes", 64 << 20,
             "PT302 threshold: lint a replicated parameter larger "
             "than this many bytes (0 = off).")

# Static numerics analyzer (paddle_tpu.analysis.numerics, ISSUE 15):
# an accumulating reduction (sum/mean/cumsum family) running in
# bf16/fp16 over at least this many elements per output lints as
# PT404 — past ~2^mantissa same-magnitude additions the low-precision
# sum stagnates.  0 disables the check.
declare_flag("numerics_reduce_elems", 65536,
             "PT404 threshold: lint a low-precision accumulating "
             "reduction over this many elements per output (0 = off).")

# Hardened inference serving runtime (paddle_tpu.serving, ISSUE 8):
# defaults for ServingConfig — overridable per-runtime, but a fleet
# rollout wants one env knob, not a code change.
declare_flag("serving_queue_depth", 64,
             "Serving admission control: max queued requests before "
             "enqueue rejects with backpressure (QueueFullError).")
declare_flag("serving_deadline_s", 0.0,
             "Default per-request deadline budget in seconds "
             "(0 = no deadline unless the request carries one).")
declare_flag("serving_watchdog_stall_s", 30.0,
             "Hang watchdog: a serving dispatch in flight longer than "
             "this triggers a flight-recorder dump and escalates per "
             "watchdog_policy.")
declare_flag("decode_slots", 8,
             "Continuous-batching decode engine (serving/decode.py): "
             "number of concurrent sequence slots one compiled decode "
             "step carries.  Every step runs the full slot width; more "
             "slots = more throughput until the step goes "
             "compute-bound.")
declare_flag("decode_max_len", 2048,
             "Decode engine ring-buffer KV-cache depth per slot "
             "(prompt + generated tokens must fit).  Fixed at engine "
             "build — it is the compiled decode step's cache shape.")
declare_flag("decode_token_budget_s", 0.0,
             "Default per-TOKEN deadline budget for decode requests: "
             "each token (including the first, i.e. TTFT) must arrive "
             "within this many seconds of the previous one or the "
             "request is shed/expired into the outcome ledger "
             "(0 = no budget unless the request carries one).")

# Request-scoped distributed tracing (paddle_tpu.monitor.tracing,
# ISSUE 18): per-request span trees through the serving tier with
# exact tail-latency attribution.  Off by default and gate-free when
# off — the dispatch fast path pays nothing (same contract as the
# flight recorder).
declare_flag("request_tracing", False,
             "Record a span tree (queue / dispatch / retry / stall / "
             "prefill / decode) for every serving request; attribution "
             "tables and SLO accounting derive exactly from the spans.")
declare_flag("trace_sample", 1.0,
             "Head-sampling rate for retaining FULL span trees of "
             "non-violating requests (0.0..1.0).  SLO violators are "
             "always retained regardless; per-request attribution "
             "component rows are always recorded.")
declare_flag("serving_slo_ms", 0.0,
             "End-to-end latency SLO per request in milliseconds: a "
             "completed request slower than this counts as an SLO "
             "violation (slo_violations counter + burn-rate gauge on "
             "/metrics, violator trees always retained).  0 = no SLO.")
declare_flag("trace_buffer", 512,
             "Capacity of the retained full-span-tree ring per serving "
             "label (violators + head-sampled); oldest trees fall out "
             "and are counted in trees_dropped.")

# Program-level graph optimizer (paddle_tpu.passes, ISSUE 9): the
# framework/ir pass-pipeline analogue.  "on" substitutes an optimized
# program (CSE / const fold / identity+scale collapse / DCE) before
# tracing, cached per (program version, fetch set, pass config) so the
# steady-state dispatch path pays one flag read + one dict probe.
declare_flag("graph_opt", "off",
             "Run the graph-optimizer pass pipeline before tracing: "
             "off | on.")
declare_flag("graph_opt_disable", "",
             "Comma-separated pass names to skip when FLAGS_graph_opt "
             "is on (e.g. 'cse,dce'); see passes.DEFAULT_PIPELINE.")

# Bucketed data-parallel gradient synchronization (transpiler.
# collective.sync_gradients): flatten gradients per dtype and psum
# fixed-capacity buckets instead of one collective per gradient — the
# fuse_all_reduce_op_pass / PyTorch-DDP gradient-bucketing design.
# Bitwise-identical to the per-gradient sync (psum is elementwise);
# 0 disables bucketing and emits one psum per gradient.
declare_flag("dp_bucket_bytes", 4 << 20,
             "Capacity in bytes of one flattened dp gradient-sync "
             "bucket (0 = one psum per gradient).")

# Fusion pass tier (paddle_tpu.passes.fuse, ISSUE 14): pattern-match
# attention / conv+bn / bias+act / layer_norm+residual subgraphs into
# the fused ops whose kernels dispatch to paddle_tpu/kernels/ (flash
# attention, Pallas layer_norm).  "train" (the default) fuses programs
# going through the dataset train loop (train_from_dataset — the zoo
# train path); "on" extends it to every executor-run train program and
# joins the fusion tier into the FLAGS_graph_opt inference pipeline;
# "off" never fuses.  With "off" (and FLAGS_amp=off) the executor is
# byte-for-byte the PR-13 dispatch path.
declare_flag("graph_opt_fuse", "train",
             "Fusion pass tier: off | train (dataset train loop only) "
             "| on (every train program + the graph_opt inference "
             "pipeline).")
declare_flag("graph_opt_fuse_disable", "",
             "Comma-separated fusion pass names to skip (e.g. "
             "'fuse_attention'); see passes.FUSION_PIPELINE.")

# AMP-by-default train path (ISSUE 14): bf16 automatic mixed precision
# via amp.rewrite_train_program on the executor's cloned substitute —
# fp32 master params in scope, white-list ops (matmul/conv/fc) compute
# in FLAGS_amp_dtype, black-list reductions pinned fp32, the PR-4
# all-finite anomaly guard as the safety net.  Same trinary as the
# fusion flag; canonical order is AMP rewrite -> fusion -> structural
# passes (enforced with a loud error when violated).
declare_flag("amp", "train",
             "Automatic mixed precision for compiled train steps: "
             "off | train (dataset train loop only) | on (every "
             "executor-run train program).")

# Inference-mode folding (passes.fold_inference): Predictor folds
# test-mode batch_norms into conv/fc weights and collapses
# scale/identity chains at load time.  Outputs are allclose — not
# bitwise — to the unfolded program (documented in README).
declare_flag("inference_fold", True,
             "Fold conv/fc+batch_norm and scale chains when loading "
             "inference models (Predictor/serving).")

# Fleet-wide observability (paddle_tpu.monitor.fleet / exporter,
# ISSUE 10).  The skew probe rides the dp step as two extra int32
# scalars per device (host pre-sync timestamp) plus one pmax+all_gather
# pair per step — each rank derives its own compute-vs-barrier-wait
# split with no host round trip.  Non-dp programs never read the flag.
declare_flag("fleet_skew", True,
             "Emit the per-step straggler/skew probe alongside the dp "
             "gradient sync (dp programs only).")

# Live Prometheus exporter: a stdlib http.server daemon thread serving
# /metrics (text format: every counter/gauge, serving p50/p99, breaker
# state, peak HBM, fleet skew) and /healthz (rc reflects breaker /
# watchdog / anomaly-guard state).  0 (default) = off: the hot path
# carries no exporter code at all, gate-free like the flight recorder.
declare_flag("metrics_port", 0,
             "Serve /metrics and /healthz on this port (0 = off).")
declare_flag("metrics_host", "127.0.0.1",
             "Bind address for the metrics exporter.  Loopback by "
             "default — the scrape body names hosts and serving "
             "labels; set 0.0.0.0 deliberately to let a fleet-level "
             "Prometheus reach it.")

# Telemetry JSONL rotation: a week-long always-on run must not fill a
# disk.  When the active segment passes the cap it is rotated to
# <path>.1 (older segments shift up) and the oldest beyond the keep
# count is deleted; read_jsonl reads rotated segments transparently.
declare_flag("telemetry_max_mb", 512,
             "Rotate the telemetry JSONL when the active segment "
             "passes this many MiB (0 = never rotate).")
declare_flag("telemetry_keep", 3,
             "How many rotated telemetry JSONL segments to keep "
             "(beyond the active one).")

# Fleet serving tier (router + replicas).  Poll/failover knobs live in
# flags so a deployment can retune them without code: a LAN fleet wants
# sub-second health gating; a cross-zone one wants fewer, patient polls.
declare_flag("fleet_health_poll_s", 0.5,
             "FleetRouter health-poll interval in seconds (0 = no "
             "background polling; call poll_once() manually).")
declare_flag("fleet_failover_attempts", 2,
             "How many ADDITIONAL replicas a request may fail over to "
             "after its first attempt fails with a transient/"
             "preemption-classified error.  Deadline and fatal "
             "failures never fail over.")
declare_flag("fleet_request_timeout_s", 30.0,
             "Socket timeout for one router->replica request hop.")

# Goodput ledger (paddle_tpu.monitor.goodput, ISSUE 20): partition the
# entire wall time of a train_from_dataset run / long Executor.run
# session into an exhaustive set of integer-ns categories (productive
# step, compile, data wait, host dispatch, checkpoint save, recovery,
# elastic transition, dp sync wait, unattributed residual) that sum
# EXACTLY to the measured wall time.  Off (default) = gate-free: the
# dispatch path pays one module-global read; on = one clock read per
# category transition.
declare_flag("goodput", False,
             "Keep the wall-clock goodput/badput attribution ledger "
             "during training runs (kind=\"goodput\" record, /metrics "
             "goodput gauges + per-category badput counters, chrome "
             "badput tracks).")

declare_flag("maxpool_mask_bwd", False,
             "Give max-pool a recompute-mask custom VJP (window passes "
             "+ shifted compares, all XLA-fusable) instead of the "
             "default select_and_scatter backward — same first-match "
             "tie semantics; a TPU bandwidth experiment knob. "
             "Restriction: custom_vjp has no JVP rule, so forward-mode "
             "AD (jax.jvp/linearize) through max-pool fails with the "
             "flag on; reverse-mode training is unaffected.")
