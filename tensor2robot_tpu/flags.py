"""Central registry of every `T2R_*` environment gate.

The framework's runtime toggles are env vars so one flip A/Bs a whole
pipeline (bench legs, regression bisects, pod-launch wrappers) — but
after PRs 1-2 the ~10 gates were read ad hoc across six modules, each
re-implementing its own parse + default. Drift between two readers of
the same flag (different defaults, different accepted spellings) is a
contract break the type system never sees; it surfaces minutes into a
pod allocation as a silently-wrong pipeline configuration.

This module is the single source of truth:

  * every flag is DECLARED once (name, kind, default, doc, owning
    module) in `_DECLARATIONS` below;
  * every read goes through the typed getters (`get_bool`, `get_int`,
    `get_enum`, `get_str`, `get_optional_int`), which parse and
    validate identically everywhere and fail fast — with the flag name
    in the message — on a bad value;
  * writes that must cross a process boundary (worker initializers,
    bench save/restore) go through `write_env` / `read_raw` /
    `restore_env` so they stay visible to the same registry;
  * the AST lint (analysis/lints.py, rule env-undeclared) fails the
    build on any `os.environ` read of a `T2R_*` key outside this file,
    so an undeclared or locally-reparsed flag cannot land.

Contribution rule (docs/static_analysis.md): adding a gate = one
`_declare(...)` line here + reads via the getters. Nothing else.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

__all__ = [
    "FlagSpec",
    "all_flags",
    "get_flag",
    "get_bool",
    "get_int",
    "get_optional_int",
    "get_enum",
    "get_str",
    "read_raw",
    "write_env",
    "restore_env",
    "describe",
]

_BOOL, _INT, _ENUM, _STR = "bool", "int", "enum", "str"


@dataclasses.dataclass(frozen=True)
class FlagSpec:
    """One declared env gate.

    Attributes:
      name: The full environment variable name (T2R_...).
      kind: 'bool' ('0'/'1'), 'int', 'enum' (one of `choices`), or 'str'.
      default: The value returned when the variable is unset. For 'bool'
        flags this is the parsed bool; for 'int' the parsed int; for
        'enum'/'str' the raw string (or None for optional strings).
      doc: One-line description of what the gate controls.
      owner: The module that owns the behavior (where the flag is
        consumed), for `t2r-check --flags` listings and the docs table.
      choices: Accepted values for 'enum' flags.
      minimum: Lower clamp for 'int' flags (values below are clamped,
        matching the pre-registry readers' max(0, ...) behavior).
    """

    name: str
    kind: str
    default: object
    doc: str
    owner: str
    choices: Optional[Tuple[str, ...]] = None
    minimum: Optional[int] = None


_REGISTRY: Dict[str, FlagSpec] = {}


def _declare(
    name: str,
    kind: str,
    default,
    doc: str,
    owner: str,
    choices: Optional[Tuple[str, ...]] = None,
    minimum: Optional[int] = None,
) -> FlagSpec:
    if name in _REGISTRY:
        raise ValueError(f"flag {name} declared twice")
    if not name.startswith("T2R_"):
        raise ValueError(f"flag {name} must be namespaced T2R_*")
    if kind == _ENUM and not choices:
        raise ValueError(f"enum flag {name} needs choices")
    spec = FlagSpec(name, kind, default, doc, owner, choices, minimum)
    _REGISTRY[name] = spec
    return spec


# -- the registry -------------------------------------------------------------
# One line per gate. Keep alphabetical; the lint only checks reads, but
# reviewers check this table against docs/static_analysis.md.

_declare(
    "T2R_AOT_EXPORT",
    _BOOL,
    True,
    "Export-side AOT executables: serialize one compiled executable per "
    "warmup bucket (per serve-quant regime too) into the export dir's "
    "aot/, keyed on artifact fingerprint + device topology "
    "(export/aot.py). 0 writes artifacts without aot/ (the pre-AOT "
    "layout).",
    "tensor2robot_tpu/export/saved_model.py",
)
_declare(
    "T2R_AOT_REQUIRE",
    _BOOL,
    False,
    "Strict AOT boots: a restore that cannot deserialize an AOT "
    "executable for EVERY warmup bucket fails loudly instead of falling "
    "back to the compile tiers — for fleets where a deploy-time compile "
    "is an SLO violation, not a slow path.",
    "tensor2robot_tpu/export/saved_model.py",
)
_declare(
    "T2R_CHAOS",
    _STR,
    None,
    "Deterministic fault-injection plan (testing/chaos.py): semicolon-"
    "separated '[scope/]site:occurrence:action[:arg]' clauses, e.g. "
    "'r0/predict:3:kill;save:2:sigkill'. Unset = no faults.",
    "tensor2robot_tpu/testing/chaos.py",
)
_declare(
    "T2R_COLLECTIVE_BLOCK",
    _INT,
    512,
    "Quantization block size (elements per scale) for quantized gradient "
    "collectives.",
    "tensor2robot_tpu/parallel/collectives.py",
    minimum=1,
)
_declare(
    "T2R_COLLECTIVE_QUANT",
    _ENUM,
    "none",
    "Gradient-collective wire format on the ZeRO-2 data-parallel path; "
    "none keeps the exact GSPMD psum byte-for-byte. fp8_e4m3/fp8_e5m2 "
    "are the blockwise fp8 formats (1 byte/element, relative rounding) "
    "with the same error-feedback residual discipline as int8.",
    "tensor2robot_tpu/parallel/collectives.py",
    choices=("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"),
)
_declare(
    "T2R_DECODE_CACHE_MB",
    _INT,
    512,
    "Decoded-image cache byte budget in MB; 0 disables the cache.",
    "tensor2robot_tpu/data/wire.py",
    minimum=0,
)
_declare(
    "T2R_DECODE_ROI",
    _BOOL,
    True,
    "Honor decode-time ROI crops; 0 restores full-frame decode exactly.",
    "tensor2robot_tpu/data/dataset.py",
)
_declare(
    "T2R_FABRIC_CONNECT_TIMEOUT_MS",
    _INT,
    2000,
    "Socket-fabric replica connect timeout (ms): how long a router-side "
    "link waits for one TCP connect to a replica's published address "
    "before the attempt fails typed (the next health probe retries).",
    "tensor2robot_tpu/serving/pool.py",
    minimum=1,
)
_declare(
    "T2R_FABRIC_HEDGE_MS",
    _INT,
    0,
    "Zone-router cross-zone hedge delay (ms): a request still pending "
    "after this long is duplicated into a DIFFERENT zone (first reply "
    "wins). Rides above the per-zone T2R_FLEET_HEDGE_MS replica hedge. "
    "0 = off.",
    "tensor2robot_tpu/serving/fabric.py",
    minimum=0,
)
_declare(
    "T2R_FLEET_HEDGE_MS",
    _INT,
    0,
    "Fleet-router hedge delay (ms): a request still pending after this "
    "long is duplicated to a second replica (first reply wins). 0 = off.",
    "tensor2robot_tpu/serving/router.py",
    minimum=0,
)
_declare(
    "T2R_FLEET_MAX_INFLIGHT",
    _INT,
    64,
    "Fleet-router per-replica in-flight cap; with every healthy replica "
    "at the cap, new requests are shed with a typed error (never queued "
    "unboundedly, never hung).",
    "tensor2robot_tpu/serving/router.py",
    minimum=1,
)
_declare(
    "T2R_FLEET_RETRIES",
    _INT,
    2,
    "Fleet-router max retry attempts (beyond the first dispatch) after a "
    "replica failure, each with jittered exponential backoff.",
    "tensor2robot_tpu/serving/router.py",
    minimum=0,
)
_declare(
    "T2R_FLEET_TRANSPORT",
    _ENUM,
    "local",
    "Fleet replica transport: local = multiprocessing queues + shared-"
    "memory slots in one process group (byte-compatible tier-1 default); "
    "socket = independent process groups speaking the shared CRC-framed "
    "wire (net/frames.py) with published-address discovery — the cross-"
    "host serving fabric.",
    "tensor2robot_tpu/serving/router.py",
    choices=("local", "socket"),
)
_declare(
    "T2R_GATE_BURST",
    _INT,
    32,
    "Gateway token-bucket depth per tenant (requests): how large an "
    "instantaneous burst a tenant may land before admission throttles "
    "it back to its refill rate.",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_GATE_CIRCUIT_COOLOFF_MS",
    _INT,
    2000,
    "Per-tenant circuit cooloff (ms): how long an open tenant circuit "
    "rejects at admission before the tenant is readmitted.",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_GATE_CIRCUIT_THRESHOLD",
    _INT,
    8,
    "Per-tenant circuit threshold: consecutive pool-side failures of one "
    "tenant's requests before its circuit opens (TenantSuspended at "
    "admission) — a rogue tenant cannot brown out the shared pool.",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_GATE_COALESCE",
    _BOOL,
    True,
    "Gateway request coalescing: bitwise-identical observations against "
    "the same pool share ONE replica dispatch (never across a "
    "model-version flip); 0 dispatches every request individually.",
    "tensor2robot_tpu/serving/gateway.py",
)
_declare(
    "T2R_GATE_DEADLINE_MS",
    _INT,
    1000,
    "Default end-to-end gateway deadline (ms) when submit() passes none; "
    "the remaining budget rides into the router and down to the replica.",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_GATE_MAX_QUEUE",
    _INT,
    512,
    "Gateway admission-queue bound per pool: beyond it the strict-"
    "priority overload policy sheds the lowest tier first (typed "
    "TierShed, bronze before gold).",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_GATE_QUOTA_RPS",
    _INT,
    100,
    "Default per-tenant admission quota (requests/s token-bucket refill) "
    "for tenant bindings that do not set an explicit quota; over-quota "
    "submissions fail typed (TenantThrottled) at admission.",
    "tensor2robot_tpu/serving/gateway.py",
    minimum=1,
)
_declare(
    "T2R_INFEED_DEPTH",
    _INT,
    2,
    "Device-prefetch depth: batches kept in flight ahead of the consumer.",
    "tensor2robot_tpu/train/infeed.py",
    minimum=1,
)
_declare(
    "T2R_LOCK_SANITIZER",
    _BOOL,
    False,
    "Instrument the threaded fabric's locks (testing/locksmith.py): "
    "runtime lock-order cycle detection, hold-time budgets, and "
    "blocking-call-under-lock reports. Off = plain threading "
    "primitives, zero overhead.",
    "tensor2robot_tpu/testing/locksmith.py",
)
_declare(
    "T2R_LOCK_HOLD_BUDGET_MS",
    _INT,
    2000,
    "Per-lock hold-time budget for the lock sanitizer, in ms. "
    "Exceeding it records a typed hold-budget violation (report only, "
    "never a kill); 0 disables the budget.",
    "tensor2robot_tpu/testing/locksmith.py",
    minimum=0,
)
_declare(
    "T2R_MULTI_EVAL_NAME",
    _STR,
    None,
    "Selects the eval dataset for MultiEvalRecordInputGenerator.",
    "tensor2robot_tpu/data/input_generators.py",
)
_declare(
    "T2R_PARSE_BACKEND",
    _ENUM,
    "thread",
    "Parse worker pool backend.",
    "tensor2robot_tpu/data/dataset.py",
    choices=("thread", "process"),
)
_declare(
    "T2R_PARSE_FAST",
    _BOOL,
    True,
    "Wire-format fast parser (SpecParser stays the per-batch fallback).",
    "tensor2robot_tpu/data/dataset.py",
)
_declare(
    "T2R_PARSE_ON_ERROR",
    _ENUM,
    "raise",
    "Data-pipeline behavior on a genuinely corrupt record mid-stream "
    "(CRC / strict-frame / proto parse failure in BOTH the fast parser "
    "and the SpecParser oracle): raise kills the consumer with the "
    "canonical error (default); skip drops the bad record(s), counts "
    "them in the dataset's stats()['records_skipped'], and yields the "
    "surviving batch.",
    "tensor2robot_tpu/data/dataset.py",
    choices=("raise", "skip"),
)
_declare(
    "T2R_PARSE_SHM",
    _BOOL,
    True,
    "Process-backend batches return via the shared-memory ring.",
    "tensor2robot_tpu/data/dataset.py",
)
_declare(
    "T2R_PARSE_WORKERS",
    _INT,
    None,
    "Parse pool size; 0 = synchronous; unset = the cores the process may "
    "run on, less one.",
    "tensor2robot_tpu/data/dataset.py",
    minimum=0,
)
_declare(
    "T2R_PLAN",
    _STR,
    "off",
    "Sharding-planner gate (parallel/planner.py): 'off' (default) keeps "
    "the hand-wired trainer path byte-for-byte; a preset name (e.g. "
    "dp_zero2_int8, dp_sp_pp — planner.preset_names()) drives the "
    "trainer from that plan with a leaf-for-leaf layout audit; 'auto' "
    "enumerates DP x SP x PP factorizations of the device count and "
    "picks the winner (memory fit first, then estimated wire bytes).",
    "tensor2robot_tpu/parallel/planner.py",
)
_declare(
    "T2R_PLAN_CACHE_DIR",
    _STR,
    None,
    "Persistent plan-cache directory for T2R_PLAN=auto "
    "(parallel/plan_cache.py): the search's winning plan + measured "
    "table are stored keyed on (model fingerprint, topology, jax "
    "version, planner schema); a later auto run on the same key "
    "deserializes the winner and performs ZERO search compiles. Unset "
    "(the default) disables the cache — every auto run searches fresh.",
    "tensor2robot_tpu/parallel/plan_cache.py",
)
_declare(
    "T2R_PLAN_MEASURE",
    _STR,
    "off",
    "Measured tier of the T2R_PLAN=auto search (parallel/planner.py): "
    "'off' (default) ranks analytically only; 'shortlist-N' compiles "
    "the top N analytic candidates' train steps (persistent compile "
    "cache bypassed), reads compiled.memory_analysis(), times a "
    "handful of real steps, and re-ranks on measured step time with "
    "memory fit as a hard gate.",
    "tensor2robot_tpu/parallel/planner.py",
)
_declare(
    "T2R_PLAN_MEASURE_STEPS",
    _INT,
    3,
    "Timed post-warmup train steps per shortlisted candidate in the "
    "measured plan search (the probe reports their median).",
    "tensor2robot_tpu/parallel/planner.py",
    minimum=1,
)
_declare(
    "T2R_PLAN_MEM_BUDGET",
    _INT,
    0,
    "Per-device memory budget in MB for T2R_PLAN=auto's factorization "
    "search; candidates whose analytic estimate exceeds it are rejected "
    "(with the estimate in the error when nothing fits). 0 = unbounded.",
    "tensor2robot_tpu/parallel/planner.py",
    minimum=0,
)
_declare(
    "T2R_POLICY_COLD_LOAD",
    _BOOL,
    True,
    "Multi-policy replicas (serving/policies.py): load a non-resident "
    "policy on first use (counted cold load, LRU eviction under the "
    "memory budget). 0 = a miss is a typed refusal (PolicyEvicted for "
    "previously-evicted policies, PolicyUnknown otherwise) — the "
    "placement layer must route to a resident replica.",
    "tensor2robot_tpu/serving/policies.py",
)
_declare(
    "T2R_POLICY_DELTA_BLOCK",
    _INT,
    512,
    "Quantization block size (elements per scale) for delta-compressed "
    "sibling payloads in the content-addressed artifact store "
    "(export/artifact_store.py); each leaf's diff-vs-base is raveled "
    "and zero-padded to a block multiple before encoding.",
    "tensor2robot_tpu/export/artifact_store.py",
    minimum=1,
)
_declare(
    "T2R_POLICY_DELTA_QUANT",
    _ENUM,
    "int8",
    "Wire regime for delta-compressed sibling payloads in the artifact "
    "store (export/artifact_store.py): per-leaf weight diffs vs the "
    "named base artifact encode through the blockwise collective codec "
    "(parallel/collectives.py). 'none' stores the diff dense-exact "
    "(dedup still applies to program/AOT blobs).",
    "tensor2robot_tpu/export/artifact_store.py",
    choices=("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"),
)
_declare(
    "T2R_POLICY_DELTA_TOL",
    _STR,
    "0.05",
    "Per-leaf parity-gate tolerance for delta payloads "
    "(export/artifact_store.py), parsed as a float: decode(delta)+base "
    "must reconstruct the leaf within this relative L-inf bound or THAT "
    "LEAF ships dense-exact (gate-fails-write-nothing — demotion is "
    "per leaf and recorded in the manifest, never a partial policy).",
    "tensor2robot_tpu/export/artifact_store.py",
)
_declare(
    "T2R_POLICY_MAX_RESIDENT",
    _INT,
    0,
    "Hard cap on the number of policies resident on one multi-policy "
    "replica (serving/policies.py); the least-recently-used idle policy "
    "is evicted to admit a new one. 0 = unbounded (the byte budget "
    "T2R_POLICY_MEM_BUDGET still applies).",
    "tensor2robot_tpu/serving/policies.py",
    minimum=0,
)
_declare(
    "T2R_POLICY_MEM_BUDGET",
    _INT,
    0,
    "Resident-policy memory budget in MB per multi-policy replica "
    "(serving/policies.py): loading a policy that would push the sum of "
    "resident policies' weight bytes over the budget first evicts "
    "least-recently-used idle policies (typed PolicyEvicted on later "
    "use when cold loads are disabled; counted cold-load reload "
    "otherwise). 0 = unbounded.",
    "tensor2robot_tpu/serving/policies.py",
    minimum=0,
)
_declare(
    "T2R_REPLAY_RETRIES",
    _INT,
    5,
    "Replay-client max retry attempts (beyond the first try) for an "
    "append/sample/stats call that failed or timed out — the service "
    "may be mid-restart after a crash; each retry backs off with "
    "jittered exponential delay.",
    "tensor2robot_tpu/replay/service.py",
    minimum=0,
)
_declare(
    "T2R_REPLAY_SAMPLER",
    _ENUM,
    "fifo",
    "Replay sampling policy: fifo cycles sealed segments in seal order "
    "(deterministic — the crash-consistency contract leans on it); "
    "prioritized draws episodes weighted by their append-time priority "
    "from a seeded RNG.",
    "tensor2robot_tpu/replay/service.py",
    choices=("fifo", "prioritized"),
)
_declare(
    "T2R_REPLAY_SEAL_BYTES",
    _INT,
    4 << 20,
    "Auto-seal the open replay segment once it holds at least this many "
    "payload bytes (whichever of the episode/byte thresholds trips "
    "first).",
    "tensor2robot_tpu/replay/service.py",
    minimum=1,
)
_declare(
    "T2R_REPLAY_SEAL_EPISODES",
    _INT,
    16,
    "Auto-seal the open replay segment once it holds this many episodes "
    "(the unsealed tail is the crash-loss bound: smaller seals = less "
    "loss, more manifest overhead).",
    "tensor2robot_tpu/replay/service.py",
    minimum=1,
)
_declare(
    "T2R_REPLAY_SHARDS",
    _INT,
    1,
    "Replay-service shard count for the online loop: 1 = the single "
    "service; >1 = consistent-hash episode placement over per-shard "
    "segment directories with sample failover and bounded append spill "
    "(replay/sharded.py).",
    "tensor2robot_tpu/replay/loop.py",
    minimum=1,
)
_declare(
    "T2R_REPLAY_SPILL_BYTES",
    _INT,
    8 << 20,
    "Client-side spill budget (bytes) for episodes addressed to an "
    "unreachable replay shard: buffered and retried in order until the "
    "shard returns; beyond the budget episodes are dropped AND counted "
    "(degraded, never silent).",
    "tensor2robot_tpu/replay/sharded.py",
    minimum=0,
)
_declare(
    "T2R_REPLAY_TRANSPORT",
    _ENUM,
    "queue",
    "Replay client/service wire: queue = supervisor-bridged mp queues "
    "(single host, the tier-1 fallback); socket = CRC-framed TCP "
    "(replay/transport.py) with per-request deadlines — the cross-host "
    "fabric the sharded bench runs on.",
    "tensor2robot_tpu/replay/service.py",
    choices=("queue", "socket"),
)
_declare(
    "T2R_SERVE_AOT",
    _BOOL,
    True,
    "Restore-side AOT executables: resolve each warmup bucket from the "
    "artifact's aot/ dir (deserialize instead of compile) with a LOUD, "
    "counted fallback to persistent-cache/fresh-trace on any key "
    "mismatch. 0 reproduces the pre-AOT restore path byte for byte.",
    "tensor2robot_tpu/export/saved_model.py",
)
_declare(
    "T2R_SERVE_BUCKETS",
    _STR,
    None,
    "Comma-separated batch-size bucket override for the policy server "
    "(unset = the export's warmup_batch_sizes).",
    "tensor2robot_tpu/serving/server.py",
)
_declare(
    "T2R_SERVE_CALIB",
    _ENUM,
    "static",
    "Activation-calibration mode for NATIVE low-precision serving "
    "exports (export/serve_quant.py): 'static' (default) bakes "
    "export-time per-layer 99.9th-percentile activation clips into the "
    "serving program as constants — zero per-dispatch activation-quant "
    "reductions (audit_quant_reduces), with per-layer demotion back to "
    "dynamic when the warmup overshoot exceeds the gate; 'dynamic' "
    "keeps the round-16 per-row max-abs quant op for op — the same "
    "serialized program bytes for models whose eligibility map round "
    "18 did not widen (conv/attention lowering is map-driven, not "
    "calib-driven: disable via T2R_SERVE_NATIVE_LAYERS/"
    "T2R_SERVE_NATIVE_ATTN for the full round-16 program).",
    "tensor2robot_tpu/export/serve_quant.py",
    choices=("static", "dynamic"),
)
_declare(
    "T2R_SERVE_NATIVE_ATTN",
    _STR,
    None,
    "Attention-head eligibility for NATIVE low-precision QK^T/PV "
    "contractions in quantized serving exports (export/serve_quant.py): "
    "unset or 'auto' = every attention module on the materialized-"
    "logits einsum path quantizes both contraction operands (per-row "
    "or static scales on the accumulator; flash/ring/ulysses heads "
    "never lower); 'none' = attention stays on the f32 einsum path; "
    "anything else = comma-separated fnmatch globs over attention "
    "module paths selecting WHICH heads lower.",
    "tensor2robot_tpu/export/serve_quant.py",
)
_declare(
    "T2R_SERVE_NATIVE_LAYERS",
    _STR,
    None,
    "Per-layer eligibility override for NATIVE low-precision matmuls in "
    "quantized serving exports (export/serve_quant.py): unset or 'auto' "
    "= the default map (2-D '.../kernel' leaves run int8/fp8 "
    "dot_general with scales applied to the accumulator); 'none' = "
    "disable native lowering (every layer dequantizes before the "
    "matmul, the pre-round-16 path); anything else = comma-separated "
    "fnmatch globs over flat param paths selecting WHICH structurally-"
    "eligible layers lower natively (parity-fragile layers stay on the "
    "dequant path).",
    "tensor2robot_tpu/export/serve_quant.py",
)
_declare(
    "T2R_SERVE_QUANT",
    _ENUM,
    "none",
    "Low-precision serving regime for exported-artifact predictors: "
    "fp16/int8/fp8_e4m3/fp8_e5m2 serve the export's blockwise-scaled "
    "quantized payload (export/serve_quant.py) with dequant fused into "
    "the jitted serving fn — and, for int8/fp8 regimes, eligible dense "
    "contractions executed NATIVELY on the quantized operands "
    "(T2R_SERVE_NATIVE_LAYERS); none is bit-exact to the unquantized "
    "serving path.",
    "tensor2robot_tpu/export/saved_model.py",
    choices=("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"),
)
_declare(
    "T2R_SERVE_DEADLINE_MS",
    _INT,
    1000,
    "Default per-request deadline (ms) when submit() passes none.",
    "tensor2robot_tpu/serving/server.py",
    minimum=1,
)
_declare(
    "T2R_SERVE_MAX_QUEUE",
    _INT,
    256,
    "Policy-server admission bound: max queued requests before the "
    "overload policy engages.",
    "tensor2robot_tpu/serving/server.py",
    minimum=1,
)
_declare(
    "T2R_SERVE_MAX_WAIT_MS",
    _INT,
    5,
    "Micro-batcher coalesce window (ms) from first queued request to "
    "dispatch.",
    "tensor2robot_tpu/serving/server.py",
    minimum=0,
)
_declare(
    "T2R_SERVE_OVERLOAD",
    _ENUM,
    "shed_oldest",
    "Full-queue policy: shed_oldest fails the oldest queued request, "
    "reject refuses the incoming one.",
    "tensor2robot_tpu/serving/server.py",
    choices=("shed_oldest", "reject"),
)
_declare(
    "T2R_SERVE_PREDICT_TIMEOUT_MS",
    _INT,
    0,
    "Per-batch predictor compute watchdog (ms) in the policy server: a "
    "predict call exceeding it fails that batch's futures with "
    "PredictTimeout and the dispatcher keeps serving. 0 = no watchdog "
    "(predict runs on the dispatcher thread).",
    "tensor2robot_tpu/serving/server.py",
    minimum=0,
)
_declare(
    "T2R_SKIP_HYPOTHESIS",
    _BOOL,
    False,
    "Skip hypothesis-driven property/fuzz tests explicitly.",
    "tests/",
)
_declare(
    "T2R_STEM_S2D",
    _ENUM,
    "auto",
    "Strided stem space-to-depth lowering; auto currently resolves off.",
    "tensor2robot_tpu/layers/s2d_conv.py",
    choices=("auto", "0", "1"),
)
_declare(
    "T2R_WIRE",
    _ENUM,
    "pickle",
    "Frame codec every SEND on the CRC-framed socket wire uses "
    "(net/frames.py; receivers auto-detect per frame from the magic). "
    "pickle is byte-identical to the pre-spec wire; spec is the "
    "zero-copy segment codec (scatter-gather sendmsg, pooled recv_into, "
    "np.frombuffer decode) both fabrics ride for array payloads.",
    "tensor2robot_tpu/net/codec.py",
    choices=("pickle", "spec"),
)
_declare(
    "T2R_WIRE_QUANT",
    _ENUM,
    "none",
    "Quantized observation payloads on the spec wire codec: float "
    "arrays ride the BlockScaledCollective blockwise format "
    "(T2R_COLLECTIVE_BLOCK elements per scale), uint8 image planes "
    "pass through untouched; each array is parity-gated at encode "
    "(rel-Linf per mode) and sent dense on a miss. none is bit-exact.",
    "tensor2robot_tpu/net/codec.py",
    choices=("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"),
)


# -- lookup -------------------------------------------------------------------


def all_flags() -> Tuple[FlagSpec, ...]:
    return tuple(_REGISTRY[name] for name in sorted(_REGISTRY))


def get_flag(name: str) -> FlagSpec:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(
            f"{name} is not a declared T2R flag; declare it in "
            "tensor2robot_tpu/flags.py (see docs/static_analysis.md)"
        )
    return spec


def _raw(spec: FlagSpec) -> Optional[str]:
    return os.environ.get(spec.name)


# -- typed getters ------------------------------------------------------------


def get_bool(name: str) -> bool:
    """'0'/'1' flags; anything else fails fast with the flag name."""
    spec = get_flag(name)
    if spec.kind != _BOOL:
        raise TypeError(f"{name} is a {spec.kind} flag, not bool")
    raw = _raw(spec)
    if raw is None:
        return bool(spec.default)
    if raw not in ("0", "1"):
        raise ValueError(f"{name} must be '0' or '1', got {raw!r}")
    return raw == "1"


def get_int(name: str) -> int:
    spec = get_flag(name)
    if spec.kind != _INT:
        raise TypeError(f"{name} is a {spec.kind} flag, not int")
    raw = _raw(spec)
    if raw is None:
        value = spec.default
        if value is None:
            raise ValueError(
                f"{name} has no default; use get_optional_int"
            )
        value = int(value)
    else:
        try:
            value = int(raw)
        except ValueError as err:
            raise ValueError(f"{name} must be an integer, got {raw!r}") from err
    if spec.minimum is not None:
        value = max(spec.minimum, value)
    return value


def get_optional_int(name: str) -> Optional[int]:
    """Int flag whose unset state is meaningful (caller picks the default)."""
    spec = get_flag(name)
    if spec.kind != _INT:
        raise TypeError(f"{name} is a {spec.kind} flag, not int")
    if _raw(spec) is None:
        return None
    return get_int(name)


def get_enum(name: str) -> str:
    spec = get_flag(name)
    if spec.kind != _ENUM:
        raise TypeError(f"{name} is a {spec.kind} flag, not enum")
    raw = _raw(spec)
    if raw is None:
        return str(spec.default)
    if raw not in spec.choices:
        raise ValueError(
            f"{name}={raw!r}: expected {'|'.join(spec.choices)}"
        )
    return raw


def get_str(name: str) -> Optional[str]:
    spec = get_flag(name)
    if spec.kind != _STR:
        raise TypeError(f"{name} is a {spec.kind} flag, not str")
    raw = _raw(spec)
    return spec.default if raw is None else raw


# -- declared writes ----------------------------------------------------------
# Some owners must WRITE a flag across a process boundary (a pool
# initializer scoping the decode-cache budget per worker; the bench
# save/flip/restore around a leg). Routing those through here keeps every
# touch of a T2R_* variable attached to the registry (and lintable).


def read_raw(name: str) -> Optional[str]:
    """The raw env string (None when unset) — save/restore bookkeeping."""
    return os.environ.get(get_flag(name).name)


def write_env(name: str, value) -> None:
    """Sets a DECLARED flag in this process's environment, validating at
    the write site — a malformed value must fail HERE, not at some later
    read in a spawned worker."""
    spec = get_flag(name)
    raw = "1" if value is True else "0" if value is False else str(value)
    if spec.kind == _ENUM and raw not in spec.choices:
        raise ValueError(f"{name}={raw!r}: expected {'|'.join(spec.choices)}")
    if spec.kind == _BOOL and raw not in ("0", "1"):
        raise ValueError(f"{name} must be '0' or '1', got {raw!r}")
    if spec.kind == _INT:
        try:
            int(raw)
        except ValueError as err:
            raise ValueError(
                f"{name} must be an integer, got {raw!r}"
            ) from err
    os.environ[spec.name] = raw


def restore_env(name: str, saved: Optional[str]) -> None:
    """Restores a flag to a value captured with read_raw (None unsets)."""
    get_flag(name)
    if saved is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = saved


def describe() -> str:
    """Human-readable registry table (t2r_check.py --flags)."""
    lines = []
    for spec in all_flags():
        default = (
            "unset"
            if spec.default is None
            else ("1" if spec.default is True else
                  "0" if spec.default is False else str(spec.default))
        )
        kind = (
            f"enum[{'|'.join(spec.choices)}]" if spec.kind == _ENUM else spec.kind
        )
        lines.append(
            f"{spec.name:22s} {kind:28s} default={default:8s} "
            f"owner={spec.owner}\n    {spec.doc}"
        )
    return "\n".join(lines)
