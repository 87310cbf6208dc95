"""Typed configuration registry.

TPU-native equivalent of the reference's three-tier config system
(ref: core/src/main/scala/org/apache/spark/internal/config/ConfigBuilder.scala:183,
ConfigEntry.scala:74, SparkConf.scala): a typed ``ConfigEntry`` registry with
documentation, version, validators, defaults and fallbacks, plus a string-map
``CycloneConf`` seeded from defaults files / environment / programmatic sets.

Unlike the reference there is no separate SQLConf tier; session-mutable
entries are marked ``mutable=True`` instead.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Generic, Iterator, List, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: Dict[str, "ConfigEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


class ConfigEntry(Generic[T]):
    """A typed configuration entry (ref: ConfigEntry.scala:74)."""

    def __init__(
        self,
        key: str,
        default: Optional[T],
        value_type: type,
        doc: str = "",
        version: str = "0.1.0",
        validator: Optional[Callable[[T], bool]] = None,
        validator_msg: str = "",
        alternatives: Optional[List[str]] = None,
        fallback: Optional["ConfigEntry[T]"] = None,
        mutable: bool = False,
    ):
        self.key = key
        self.default = default
        self.value_type = value_type
        self.doc = doc
        self.version = version
        self.validator = validator
        self.validator_msg = validator_msg
        self.alternatives = alternatives or []
        self.fallback = fallback
        self.mutable = mutable
        with _REGISTRY_LOCK:
            if key in _REGISTRY:
                raise ValueError(f"Config entry already registered: {key}")
            _REGISTRY[key] = self

    def _convert(self, raw: Any) -> T:
        t = self.value_type
        if isinstance(raw, t) and not (t is int and isinstance(raw, bool)):
            return raw
        s = str(raw)
        if t is bool:
            if s.lower() in ("true", "1", "yes"):
                return True  # type: ignore[return-value]
            if s.lower() in ("false", "0", "no"):
                return False  # type: ignore[return-value]
            raise ValueError(f"{self.key}: cannot parse boolean from {raw!r}")
        if t is int:
            return int(s)  # type: ignore[return-value]
        if t is float:
            return float(s)  # type: ignore[return-value]
        if t is str:
            return s  # type: ignore[return-value]
        raise TypeError(f"{self.key}: unsupported config type {t}")

    def read_from(self, conf: "CycloneConf") -> T:
        for k in [self.key] + self.alternatives:
            if conf.contains_raw(k):
                v = self._convert(conf.get_raw(k))
                if self.validator is not None and not self.validator(v):
                    raise ValueError(
                        f"Invalid value {v!r} for {self.key}: {self.validator_msg}"
                    )
                return v
        if self.fallback is not None:
            return self.fallback.read_from(conf)
        if self.default is None:
            raise KeyError(f"Config {self.key} is not set and has no default")
        return self.default


class ConfigBuilder:
    """Fluent builder (ref: ConfigBuilder.scala:183)."""

    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._version = "0.1.0"
        self._validator: Optional[Callable] = None
        self._validator_msg = ""
        self._alternatives: List[str] = []
        self._mutable = False

    def doc(self, d: str) -> "ConfigBuilder":
        self._doc = d
        return self

    def version(self, v: str) -> "ConfigBuilder":
        self._version = v
        return self

    def with_alternative(self, key: str) -> "ConfigBuilder":
        self._alternatives.append(key)
        return self

    def check_value(self, fn: Callable, msg: str) -> "ConfigBuilder":
        self._validator = fn
        self._validator_msg = msg
        return self

    def mutable(self) -> "ConfigBuilder":
        self._mutable = True
        return self

    def _make(self, default, value_type, fallback=None) -> ConfigEntry:
        return ConfigEntry(
            self._key, default, value_type, self._doc, self._version,
            self._validator, self._validator_msg, self._alternatives,
            fallback, self._mutable,
        )

    def int_conf(self, default: Optional[int] = None) -> ConfigEntry[int]:
        return self._make(default, int)

    def float_conf(self, default: Optional[float] = None) -> ConfigEntry[float]:
        return self._make(default, float)

    def bool_conf(self, default: Optional[bool] = None) -> ConfigEntry[bool]:
        return self._make(default, bool)

    def str_conf(self, default: Optional[str] = None) -> ConfigEntry[str]:
        return self._make(default, str)

    def fallback_conf(self, parent: ConfigEntry) -> ConfigEntry:
        return self._make(None, parent.value_type, fallback=parent)


class CycloneConf:
    """String-keyed configuration map with typed reads.

    Mirrors SparkConf semantics (set/get/contains, env seeding via
    ``CYCLONE_*`` variables, clone) on top of the typed registry.
    """

    ENV_PREFIX = "CYCLONE_CONF_"

    def __init__(self, load_defaults: bool = True):
        self._settings: Dict[str, str] = {}
        self._lock = threading.Lock()
        if load_defaults:
            # CYCLONE_CONF_cyclone__eventLog__enabled=true → cyclone.eventLog.enabled
            # (case preserved; '__' separates dotted segments)
            for k, v in os.environ.items():
                if k.startswith(self.ENV_PREFIX):
                    key = k[len(self.ENV_PREFIX):].replace("__", ".")
                    self._settings[key] = v

    def set(self, key, value) -> "CycloneConf":
        k = key.key if isinstance(key, ConfigEntry) else key
        with self._lock:
            self._settings[k] = str(value)
        return self

    def set_if_missing(self, key, value) -> "CycloneConf":
        k = key.key if isinstance(key, ConfigEntry) else key
        with self._lock:
            self._settings.setdefault(k, str(value))
        return self

    def remove(self, key) -> "CycloneConf":
        k = key.key if isinstance(key, ConfigEntry) else key
        with self._lock:
            self._settings.pop(k, None)
        return self

    def contains_raw(self, key: str) -> bool:
        return key in self._settings

    def get_raw(self, key: str) -> str:
        return self._settings[key]

    def get(self, key, default: Any = None) -> Any:
        if isinstance(key, ConfigEntry):
            return key.read_from(self)
        entry = _REGISTRY.get(key)
        if entry is not None:
            # registered keys always get typed conversion + validation,
            # whether set or defaulted
            try:
                return entry.read_from(self)
            except KeyError:
                pass
        elif key in self._settings:
            return self._settings[key]
        if default is not None:
            return default
        raise KeyError(key)

    def get_all(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._settings)

    def clone(self) -> "CycloneConf":
        c = CycloneConf(load_defaults=False)
        c._settings = dict(self._settings)
        return c

    def __iter__(self) -> Iterator:
        return iter(self._settings.items())


def registered_entries() -> Dict[str, ConfigEntry]:
    with _REGISTRY_LOCK:
        return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Core entries (analog of internal/config/package.scala's centralized registry)
# ---------------------------------------------------------------------------

APP_NAME = ConfigBuilder("cyclone.app.name").doc("Application name.").str_conf("cyclone-app")

MASTER = (
    ConfigBuilder("cyclone.master")
    .doc("Mesh master: 'local-mesh[N]' for an N-device host-platform mesh, "
         "'tpu' for all attached TPU devices, 'multihost' for jax.distributed.")
    .str_conf("tpu")
)

DEFAULT_PARALLELISM = (
    ConfigBuilder("cyclone.default.parallelism")
    .doc("Default number of dataset partitions (0 = number of mesh devices).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(0)
)

BLOCK_SIZE_MAX_MEM = (
    ConfigBuilder("cyclone.dataset.blockSizeInMB")
    .doc("Max memory per instance block in MB "
         "(ref: ml/feature/Instance.scala:146 blokifyWithMaxMemUsage).")
    .float_conf(0.0)
)

AGGREGATION_DEPTH = (
    ConfigBuilder("cyclone.treeAggregate.depth")
    .doc("Depth of hierarchical reduction across DCN slices "
         "(ref: RDD.scala:1223 treeAggregate).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(2)
)

DEVICE_DTYPE = (
    ConfigBuilder("cyclone.compute.dtype")
    .doc("Accumulation dtype for device kernels; float32 keeps MXU throughput "
         "while matching JVM double loss curves to ~1e-6 relative.")
    .str_conf("float32")
)

DATA_DTYPE = (
    ConfigBuilder("cyclone.data.dtype")
    .doc("Storage dtype of the DATA tier — every materialized design "
         "matrix (dataset blocks, managed-tier spills, OvR label stacks). "
         "'auto' (default) is bfloat16 — fits are bandwidth-bound "
         "(BENCH r03-r05: 71% of the measured HBM streaming ceiling at "
         "0.096% MFU), so halving X's bytes halves the sweep — EXCEPT "
         "under jax x64 (the CPU parity/test config), where it resolves "
         "to float64 so reference-parity suites are untouched. All "
         "aggregators and kernels upcast to the float32 accumulator "
         "(cyclone.compute.dtype) inside the kernel; X is never "
         "materialized wider than this tier. 'float32' opts out and "
         "restores the pre-bf16 byte-identical sweep; 'float64' is only "
         "meaningful under x64 (silently canonicalized to f32 otherwise "
         "— graftlint JX004 polices that drift). Resolved when a dataset "
         "is materialized; mutable for the next dataset, not "
         "retroactively. The SECOND precision rung: 'auto8' resolves to "
         "float8_e4m3fn (1 byte, per-column scales at accumulator width, "
         "fp32 in-kernel accumulation) for fp8-capable estimators "
         "(LogisticRegression, LinearRegression l-bfgs) and to bfloat16 "
         "for everything else — except under x64, where it keeps the "
         "parity tier like 'auto'; 'float8' forces the same split through "
         "parity configs (the acceptance suites use it). fp8-capable fits "
         "carry a pre-fit envelope probe that falls back to bf16 (event "
         "PrecisionFallback + FitProfile.fp8_fallbacks) when e4m3's 3-bit "
         "mantissa would break the documented accuracy envelope — see "
         "docs/mixed-precision.md.")
    .check_value(lambda v: v in ("auto", "auto8", "bfloat16", "float8",
                                 "float32", "float64"),
                 "must be auto, auto8, bfloat16, float8, float32 or float64")
    .mutable()
    .str_conf("auto")
)

EVENT_LOG_ENABLED = (
    ConfigBuilder("cyclone.eventLog.enabled")
    .doc("Write the structured event journal to disk "
         "(ref: EventLoggingListener.scala:50).")
    .bool_conf(False)
)

EVENT_LOG_DIR = (
    ConfigBuilder("cyclone.eventLog.dir").doc("Event journal directory.").str_conf("/tmp/cyclone-events")
)

CHECKPOINT_DIR = (
    ConfigBuilder("cyclone.checkpoint.dir")
    .doc("Directory for dataset/optimizer checkpoints "
         "(ref: RDD.scala:1631 checkpoint).")
    .str_conf("")
)

HEARTBEAT_INTERVAL_MS = (
    ConfigBuilder("cyclone.executor.heartbeatInterval")
    .doc("Host-worker heartbeat interval in ms (ref: HeartbeatReceiver).")
    .int_conf(10000)
)

DRIVER_HEARTBEAT_ADDRESS = (
    ConfigBuilder("cyclone.driver.heartbeatAddress")
    .doc("host:port of the driver's HeartbeatServer. When set, this process "
         "runs a HeartbeatSender pinging it every "
         "cyclone.executor.heartbeatInterval ms — the over-the-wire worker "
         "liveness loop (ref: HeartbeatReceiver.scala:37). Empty = no "
         "cross-process heartbeats (single-host runs).")
    .str_conf("")
)

WORKER_ID = (
    ConfigBuilder("cyclone.worker.id")
    .doc("Identity reported in heartbeats; defaults to host:pid.")
    .str_conf("")
)

NETWORK_TIMEOUT_MS = (
    ConfigBuilder("cyclone.network.timeout")
    .doc("Control-plane RPC / worker-liveness timeout in ms. Must be well "
         "above the heartbeat interval or jitter expires healthy workers "
         "(the reference defaults to 120s vs a 10s heartbeat).")
    .int_conf(120000)
)

LBFGS_DEVICE_CHUNK = (
    ConfigBuilder("cyclone.ml.lbfgs.deviceChunk")
    .doc("L-BFGS iterations fused into one device dispatch for eligible "
         "fits (dense tier, standardized-or-no L2, no L1/bounds/"
         "checkpointing). 0 disables the chunked optimizer (host loop with "
         "fused line search, one dispatch per iteration).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(16)
)

USE_PALLAS_KERNELS = (
    ConfigBuilder("cyclone.ml.usePallasKernels")
    .doc("Route the eligible dense sweeps — binomial LogisticRegression "
         "(serial AND stacked), multinomial LogisticRegression over a "
         "resident bf16 X, the LinearRegression l-bfgs objective, "
         "the RowMatrix Gramian and the KMeans assignment step — through "
         "the hand-written fused Pallas kernels (ops/kernels.py) instead "
         "of the XLA-fused jnp aggregators. 'auto' (default) makes the "
         "fused kernels the DEFAULT sweep on natively-lowered backends "
         "(TPU): one VMEM-resident row pass per loss/grad evaluation, "
         "narrow (bf16) blocks read at storage width with fp32 in-kernel "
         "accumulation (which twin is faster at which shape is not "
         "measured on the current machine). Everywhere else 'auto' keeps "
         "the XLA path. 'true'/'false' force one path for every eligible "
         "estimator; 'true' on a backend that cannot lower Mosaic raises "
         "— the package never runs the Pallas interpreter on its own.")
    .check_value(lambda v: str(v).lower() in ("auto", "true", "false"),
                 "must be auto, true or false")
    .str_conf("auto")
)

SHUFFLE_SPILL_ROW_BUDGET = (
    ConfigBuilder("cyclone.shuffle.spill.rowBudget")
    .doc("Values held in memory per host-shuffle bucket before spilling a "
         "sorted compressed run to disk (ref: ExternalAppendOnlyMap.scala:55 "
         "/ spark.shuffle.spill).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1 << 20)
)

AUTH_SECRET = (
    ConfigBuilder("cyclone.authenticate.secret")
    .doc("Shared secret for the TCP fabric (exchange, deploy, heartbeats, "
         "SQL server): every connection performs a mutual HMAC-SHA256 "
         "challenge-response before any protocol byte (the role of "
         "spark.authenticate / SaslRpcHandler.java:44). Empty = open "
         "fabric. Spawned daemons inherit via CYCLONE_AUTH_SECRET.")
    .str_conf("")
)

SQL_WAREHOUSE_DIR = (
    ConfigBuilder("cyclone.sql.warehouse.dir")
    .doc("Warehouse directory for the PERSISTENT catalog (Spark's "
         "spark.sql.warehouse.dir; the metastore analog — "
         "HiveExternalCatalog.scala:56). When set, CREATE TABLE AS / "
         "INSERT INTO write table metadata + parquet parts here and "
         "survive process restart; empty = in-memory tables only.")
    .str_conf("")
)

ADAPTIVE_ENABLED = (
    ConfigBuilder("cyclone.sql.adaptive.enabled")
    .doc("Adaptive query execution over the exchange fabric: runtime size "
         "statistics pick broadcast joins and coalesce small shuffle "
         "output partitions (ref AdaptiveSparkPlanExec).")
    .bool_conf(True)
)

AUTO_BROADCAST_JOIN_THRESHOLD = (
    ConfigBuilder("cyclone.sql.autoBroadcastJoinThreshold")
    .doc("Max bytes for a join side to be broadcast to every process "
         "instead of hash-exchanging both sides (Spark's conf name and "
         "10 MB default; -1 disables).")
    .int_conf(10 * 1024 * 1024)
)

SKEW_JOIN_ENABLED = (
    ConfigBuilder("cyclone.sql.adaptive.skewJoin.enabled")
    .doc("AQE skew-join handling (Spark's conf name; ref "
         "OptimizeSkewedJoin.scala:55): a shuffle-join bucket whose "
         "byte estimate exceeds skewedPartitionFactor x the median AND "
         "skewedPartitionThresholdInBytes is SPLIT across processes — "
         "the splittable side's rows spread round-robin while the other "
         "side's rows for that bucket are duplicated everywhere.")
    .bool_conf(True)
)

SKEW_JOIN_FACTOR = (
    ConfigBuilder("cyclone.sql.adaptive.skewJoin.skewedPartitionFactor")
    .doc("A bucket is skew-eligible when its size exceeds this factor "
         "times the median bucket size (Spark's default 5).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(5)
)

SKEW_JOIN_THRESHOLD = (
    ConfigBuilder(
        "cyclone.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes")
    .doc("Minimum estimated bucket bytes before skew splitting applies "
         "(Spark's default 256m).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(256 * 1024 * 1024)
)

ADVISORY_PARTITION_BYTES = (
    ConfigBuilder("cyclone.sql.adaptive.advisoryPartitionSizeInBytes")
    .doc("Byte target for AQE post-shuffle coalescing (Spark's conf name "
         "and semantics; CoalesceShufflePartitions): adjacent small "
         "output partitions merge until their ESTIMATED bytes reach "
         "this. 0 falls back to the row-count target "
         "(advisoryPartitionRows).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(64 * 1024 * 1024)
)

ADVISORY_PARTITION_ROWS = (
    ConfigBuilder("cyclone.sql.adaptive.advisoryPartitionRows")
    .doc("Row-count FALLBACK for AQE post-shuffle coalescing, applied "
         "only when advisoryPartitionSizeInBytes is set to 0 — the byte "
         "target (Spark's semantics) takes precedence by default.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1 << 16)
)

STORAGE_DEVICE_BUDGET = (
    ConfigBuilder("cyclone.storage.deviceBudget")
    .doc("Byte budget for DEVICE-tier managed datasets (context-owned "
         "StorageManager ≈ BlockManager memory store). Exceeding it "
         "demotes the least-recently-used managed dataset to the host "
         "tier. 0 = unbounded.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(0)
)

STORAGE_HOST_BUDGET = (
    ConfigBuilder("cyclone.storage.hostBudget")
    .doc("Byte budget for HOST-tier managed datasets; past it, LRU "
         "datasets demote to disk spill files. 0 = unbounded.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(0)
)

EXCHANGE_ADDRESSES = (
    ConfigBuilder("cyclone.exchange.addresses")
    .doc("Comma-separated host:port exchange endpoints, one per cooperating "
         "process, identical on every process. When set (with "
         "cyclone.exchange.rank), host-tier shuffles — "
         "PartitionedDataset.group_by_key/reduce_by_key and SQL "
         "Aggregate/Join — route cross-process through the HashExchange "
         "fabric (≈ ShuffleExchangeExec + block transfer); empty = "
         "single-process shuffles.")
    .str_conf("")
)

EXCHANGE_RANK = (
    ConfigBuilder("cyclone.exchange.rank")
    .doc("This process's index into cyclone.exchange.addresses.")
    .int_conf(-1)
)

EXCHANGE_NUM_BUCKETS = (
    ConfigBuilder("cyclone.exchange.numBuckets")
    .doc("Hash buckets per exchange round (≈ shuffle partitions; bucket b "
         "is owned by process b % n_processes).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(64)
)

TASK_MAX_FAILURES = (
    ConfigBuilder("cyclone.task.maxFailures")
    .doc("Retries per step before aborting (ref: TaskSetManager.scala:58).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(4)
)

MATMUL_PRECISION = (
    ConfigBuilder("cyclone.compute.matmulPrecision")
    .doc("Aggregator matmul precision: 'highest' (default) = multi-pass f32 "
         "on the MXU, matching the reference's f64 loss curves to ~1e-6; "
         "'default' = the backend's native (bf16-multiply) mode. Measured "
         "NEUTRAL for gemv-shaped binary aggregators on v5e (they are "
         "HBM-bound); only consider it for genuinely MXU-bound shapes "
         "(wide multinomial). Resolved when an aggregator is built.")
    .check_value(lambda v: v in ("highest", "default"),
                 "must be 'highest' or 'default'")
    .str_conf("highest")
)

METRICS_SINKS = (
    ConfigBuilder("cyclone.metrics.sinks")
    .doc("Comma-separated metric sinks: console, csv, prometheus "
         "(ref: metrics/MetricsSystem.scala:70 + conf/metrics.properties).")
    .str_conf("")
)

METRICS_PERIOD_S = (
    ConfigBuilder("cyclone.metrics.period")
    .doc("Push-sink report period in seconds (ref: CsvSink pollPeriod).")
    .float_conf(10.0)
)

METRICS_CSV_DIR = (
    ConfigBuilder("cyclone.metrics.csv.dir")
    .doc("Directory for the CSV metrics sink.")
    .str_conf("/tmp/cyclone-metrics")
)

PLUGINS = (
    ConfigBuilder("cyclone.plugins")
    .doc("Comma-separated plugin class paths loaded at context start "
         "(ref: api/plugin/SparkPlugin.java:37, spark.plugins).")
    .str_conf("")
)

PROMETHEUS_PORT = (
    ConfigBuilder("cyclone.metrics.prometheus.port")
    .doc("Port for the pull-based /metrics endpoint; 0 picks a free port "
         "(ref: PrometheusServlet.scala).")
    .int_conf(0)
)

MEMORY_BUDGET_FRACTION = (
    ConfigBuilder("cyclone.memory.budgetFraction")
    .doc("Compile-time memory budget guard: when a program's predicted "
         "peak HBM (XLA memory_analysis: arguments + outputs + "
         "temporaries + generated code, per device) exceeds this fraction "
         "of device memory, a MemoryBudgetExceeded event is posted and "
         "the chunked L-BFGS paths shrink deviceChunk proportionally "
         "instead of OOMing. Warn-only by default (see "
         "cyclone.memory.budgetAction). Scope: the chunked L-BFGS "
         "programs are guarded whenever this key is set explicitly or "
         "tracing is enabled; tree_aggregate and fused line-search "
         "programs are checked as part of the tracing harvest only — "
         "their untraced dispatch path stays one global read and never "
         "calls XLA's cost analysis.")
    .check_value(lambda v: 0 < v <= 1.0, "must be in (0, 1]")
    .float_conf(0.9)
)

MEMORY_BUDGET_ACTION = (
    ConfigBuilder("cyclone.memory.budgetAction")
    .doc("What an exceeded memory budget does beyond the event + chunk "
         "degradation: 'warn' (default) never raises; 'raise' throws "
         "MemoryBudgetError once degradation options are exhausted (the "
         "chunked L-BFGS guard degrades first and raises only if chunk 1 "
         "is still over budget; sites with nothing to degrade raise "
         "before dispatching the oversized program).")
    .check_value(lambda v: v in ("warn", "raise"),
                 "must be 'warn' or 'raise'")
    .str_conf("warn")
)

MEMORY_DEVICE_BYTES = (
    ConfigBuilder("cyclone.memory.deviceBytes")
    .doc("Per-device memory bytes the budget guard divides into. 0 (the "
         "default) auto-detects: device.memory_stats()['bytes_limit'] "
         "where the backend reports it (TPU/GPU), total host RAM for "
         "host-platform devices (CPU).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(0)
)

SERVING_MAX_BATCH = (
    ConfigBuilder("cyclone.serving.maxBatch")
    .doc("Upper bound on coalesced rows per serving dispatch. The model "
         "server AOT-compiles one predict program per power-of-two row "
         "bucket up to (the next power of two >=) this value at "
         "registration, so no request ever pays an XLA compile.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(64)
)

SERVING_WINDOW_MS = (
    ConfigBuilder("cyclone.serving.windowMs")
    .doc("Latency-bounded batching window in milliseconds (Clipper-style "
         "adaptive micro-batching): once a request is queued, the "
         "batcher waits at most this long for more requests to the same "
         "model before dispatching the coalesced batch. 0 dispatches "
         "immediately (no coalescing beyond what is already queued).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(5.0)
)

SERVING_DTYPE = (
    ConfigBuilder("cyclone.serving.dtype")
    .doc("Float dtype serving predict programs compute in. 'auto' (the "
         "default) resolves to the accumulator tier — float64 under jax "
         "x64, else float32. Request payloads and model parameters are "
         "cast to this width at the serving boundary; the bf16 data tier "
         "never applies to request batches (they are latency-, not "
         "bandwidth-bound, and scoring accuracy is part of the contract). "
         "'float64' requires jax x64 — without it the server downgrades "
         "to float32 with a warning rather than let XLA canonicalize f64 "
         "inputs to f32 silently.")
    .check_value(lambda v: v in ("auto", "float32", "float64"),
                 "must be 'auto', 'float32' or 'float64'")
    .str_conf("auto")
)

SERVING_MAX_QUEUE = (
    ConfigBuilder("cyclone.serving.maxQueue")
    .doc("Backpressure bound: maximum requests queued per registered "
         "model. Submissions past it fail fast with ServingOverloaded "
         "(503) instead of growing the queue without limit.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1024)
)

SERVING_SHED_AFTER_MS = (
    ConfigBuilder("cyclone.serving.shedAfterMs")
    .doc("Admission-control patience: when the HBM budget guard predicts "
         "a dispatch would not fit (cyclone.memory.budgetFraction x "
         "device memory), the batch is re-queued and re-checked each "
         "batching window until its oldest request has waited this long, "
         "then every request in it is shed with ServingOverloaded (503). "
         "Serving never raises MemoryBudgetError and never dispatches a "
         "program the guard predicts will OOM.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(1000.0)
)

SERVING_MAX_RETRIES = (
    ConfigBuilder("cyclone.serving.maxRetries")
    .doc("Dispatch retries for TRANSIENT failures (resilience "
         "classification) before the batch is shed with a 5xx "
         "ServingError. Permanent failures shed immediately.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(3)
)

SERVING_QUANTIZE = (
    ConfigBuilder("cyclone.serving.quantize")
    .doc("Serve QUANTIZED predict programs: coefficient tensors stored "
         "fp8 (e4m3) with per-margin-row scales at serving dtype, "
         "dequantized inside the compiled kernel (one elementwise "
         "multiply — the per-row reduction stays independent of the "
         "batch dim, so bucket padding remains bitwise-neutral). Cuts "
         "each bucket program's parameter HBM ~4-8x, so the PR-5/PR-8 "
         "admission path fits strictly more gang models under the same "
         "cyclone.memory.budgetFraction. Margins round to e4m3's 3-bit "
         "mantissa (~6 percent relative per coefficient) — predictions at the "
         "decision boundary can flip; see docs/serving.md for the "
         "envelope. Off by default.")
    .bool_conf(False)
)

OOCORE_MODE = (
    ConfigBuilder("cyclone.oocore.mode")
    .doc("Out-of-core streaming fit mode (oocore/): 'auto' (default) keeps "
         "in-core fits but DEGRADES to the streaming epoch engine when the "
         "memory budget guard's chunk-halving bottoms out at deviceChunk=1 "
         "with the program still over budget (instead of warn/raise); "
         "'force' routes every eligible dense fit through the streaming "
         "path (each loss/grad evaluation is one double-buffered epoch "
         "over host shards); 'off' disables streaming entirely — the "
         "guard's pre-oocore warn/raise behavior applies.")
    .check_value(lambda v: v in ("auto", "force", "off"),
                 "must be auto, force or off")
    .mutable()
    .str_conf("auto")
)

OOCORE_SHARD_ROWS = (
    ConfigBuilder("cyclone.oocore.shardRows")
    .doc("Rows per out-of-core shard. Every shard is padded to ONE fixed "
         "(padRows, d) geometry (zero-weight padding rows, masked out of "
         "the psums), so a single compiled per-shard program serves the "
         "whole epoch; host staging peaks at O(shardRows · d), never "
         "O(n · d). Sized so one shard's device footprint is well under "
         "the memory budget while staying large enough that transfer "
         "latency amortizes (the double buffer hides it behind compute).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(65536)
)

OOCORE_PREFETCH_DEPTH = (
    ConfigBuilder("cyclone.oocore.prefetchDepth")
    .doc("Staged shards in flight ahead of compute (the pinned ring): 2 = "
         "classic double buffering — shard N+1's host read + h2d transfer "
         "overlaps shard N's compute. Device-resident shard copies are "
         "bounded by depth + 1; higher values only help when staging "
         "jitter exceeds one shard's compute time.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(2)
)

OOCORE_SHUFFLE = (
    ConfigBuilder("cyclone.oocore.shuffle")
    .doc("Shuffle shard ORDER per streamed-SGD epoch (seeded permutation "
         "keyed on the optimizer seed x step, so a fixed seed replays "
         "exactly). The epoch's accumulated gradient is order-invariant "
         "up to float summation order — parity against a fixed-order run "
         "is pinned — but staged shards hit the device in permuted order, "
         "the reference's sample-without-materialize story. Off keeps "
         "the fixed sequential order.")
    .bool_conf(False)
)

OOCORE_MAX_RETRIES = (
    ConfigBuilder("cyclone.oocore.maxRetries")
    .doc("Retries for a TRANSIENT shard-staging failure (resilience "
         "classification; seeded backoff) before the epoch aborts. "
         "Permanent failures abort immediately with the stream drained "
         "and the staging thread released — never a hang.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(3)
)

OOCORE_DIR = (
    ConfigBuilder("cyclone.oocore.dir")
    .doc("Directory for out-of-core shard files (npz, data-tier packed). "
         "Empty = the system temp dir. Shard sets built by the engine own "
         "their files and remove them on close/GC.")
    .str_conf("")
)

OOCORE_STREAM_DTYPE = (
    ConfigBuilder("cyclone.oocore.streamDtype")
    .doc("Storage dtype for out-of-core shards — the PRECISION RUNG of the "
         "host→device stream (docs/out-of-core.md 'Precision rungs'). "
         "'auto' (default) follows cyclone.data.dtype, including the fp8 "
         "tiers: under auto8/float8 the spill-time envelope probe "
         "(instance.fp8_probe_ok over the write-pass moments) decides "
         "fp8-vs-bf16 per shard SET — one geometry, one program — with "
         "the bf16 fallback surfaced as a PrecisionFallback event. "
         "'bfloat16' pins the bf16 rung; 'float8' requests e4m3 codes + "
         "per-column scales whenever the probe allows (the probe still "
         "gates — codes that would break the documented envelope fall "
         "back visibly, never silently).")
    .check_value(lambda v: v in ("auto", "bfloat16", "float8"),
                 "must be auto, bfloat16 or float8")
    .mutable()
    .str_conf("auto")
)

OOCORE_CACHE_BYTES = (
    ConfigBuilder("cyclone.oocore.cacheBytes")
    .doc("Byte bound for the shard-set reuse cache (oocore/cache.py): "
         "spilled shard sets are keyed by content hash (source dataset "
         "identity + stream tier + pad geometry), so CV folds, "
         "TrainValidationSplit and warm-start re-fits ATTACH to the "
         "existing spill instead of re-blocking and re-writing it — the "
         "second fit re-streams 0 spill-write bytes. LRU-evicted past the "
         "bound; live streams pin their entries (refcount), and every "
         "attach is integrity-checked per shard (sha256 — a corrupt entry "
         "is evicted and rebuilt, chaos-covered). 0 disables reuse.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .mutable()
    .int_conf(1 << 30)
)

TRACE_ENABLED = (
    ConfigBuilder("cyclone.trace.enabled")
    .doc("Enable step-level tracing (observe/): hierarchical spans over "
         "compile/dispatch/collective/transfer/checkpoint, per-fit "
         "FitProfiles in the status store, Chrome-trace export. Off by "
         "default; the disabled cost at every instrumentation site is one "
         "module-global read. The CYCLONE_TRACE env var (any truthy value) "
         "also enables it.")
    .bool_conf(False)
)

TRACE_DIR = (
    ConfigBuilder("cyclone.trace.dir")
    .doc("When set (and tracing is enabled), the context exports "
         "<dir>/<app_id>.trace.json — Chrome Trace Event Format, loadable "
         "in Perfetto — on stop().")
    .str_conf("")
)

TRACE_MAX_SPANS = (
    ConfigBuilder("cyclone.trace.maxSpans")
    .doc("Span buffer bound (a RING: past it the OLDEST span is dropped "
         "and counted — spans_dropped in the export header and "
         "FitProfile — so a long job always keeps its recent window).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(100_000)
)

FLIGHT_ENABLED = (
    ConfigBuilder("cyclone.telemetry.flight.enabled")
    .doc("Always-on flight recorder (observe/flight.py): when full "
         "tracing is off, the context installs a bounded ring of recent "
         "spans that records at near-zero cost (no XLA cost harvest, no "
         "metrics bridge — the trace_overhead BENCH field pins the "
         "number) and freezes/dumps its window on triggers: chaos fault "
         "firing, MeshSupervisor rebuild, serving shed, SLO breach. "
         "Dumps are written under cyclone.trace.dir when set; the last "
         "few stay readable in memory either way.")
    .bool_conf(True)
)

FLIGHT_RING_SPANS = (
    ConfigBuilder("cyclone.telemetry.flight.ringSpans")
    .doc("Flight-recorder ring size in spans — the window a triggered "
         "dump preserves.")
    .check_value(lambda v: v >= 16, "must be >= 16")
    .int_conf(2048)
)

FLIGHT_MIN_INTERVAL_MS = (
    ConfigBuilder("cyclone.telemetry.flight.minIntervalMs")
    .doc("Flight-dump throttle: triggers within this window of the "
         "previous dump only count, they do not re-dump (a shed burst "
         "freezes ONE window, not one per 503).")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(1000.0)
)

COLLECT_ADDRESS = (
    ConfigBuilder("cyclone.telemetry.collect.address")
    .doc("host:port of a TraceCollector (observe/collect.py). When set, "
         "the context enables tracing (if not already on), adopts the "
         "CYCLONE_TRACE_ID / CYCLONE_TRACE_PARENT distributed-trace "
         "context from the environment, and runs a SpanShipper that "
         "drains the span ring to the collector — deploy.submit_app "
         "seeds this (env conf channel) for every launched app when the "
         "submitting process runs a collector. Empty = no shipping.")
    .str_conf("")
)

COLLECT_INTERVAL_MS = (
    ConfigBuilder("cyclone.telemetry.collect.intervalMs")
    .doc("SpanShipper drain/ship period in milliseconds.")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(500.0)
)

COLLECT_MAX_BATCH = (
    ConfigBuilder("cyclone.telemetry.collect.maxBatch")
    .doc("Spans per shipped batch; an unreachable collector buffers up "
         "to 16x this, then drops oldest (drop-counted) — shipping never "
         "blocks a recording site.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(4096)
)

SKEW_ENABLED = (
    ConfigBuilder("cyclone.telemetry.skew.enabled")
    .doc("Online straggler/skew detection (observe/skew.py): rolling "
         "median + MAD over per-lane step times (out-of-core shard "
         "staging, serving model lanes, per-worker heartbeat RTT). "
         "Latched StragglerDetected / SloBreach events post to the "
         "listener bus (status store 'skew' list, /api/v1/skew, web UI) "
         "and to subscribers (MeshSupervisor.attach_skew — the elastic "
         "scheduler's mitigation input, ROADMAP item 4).")
    .bool_conf(True)
)

SKEW_WINDOW = (
    ConfigBuilder("cyclone.telemetry.skew.window")
    .doc("Rolling samples kept per (group, lane) for the skew medians.")
    .check_value(lambda v: v >= 4, "must be >= 4")
    .int_conf(64)
)

SKEW_MIN_SAMPLES = (
    ConfigBuilder("cyclone.telemetry.skew.minSamples")
    .doc("Samples a lane needs before it participates in straggler "
         "comparison — below it the detector stays silent (cold lanes "
         "must not convict or be convicted).")
    .check_value(lambda v: v >= 2, "must be >= 2")
    .int_conf(8)
)

SKEW_MAD_FACTOR = (
    ConfigBuilder("cyclone.telemetry.skew.madFactor")
    .doc("A lane is a straggler only when its rolling median exceeds the "
         "group median by this many MADs (AND by relFactor x the median "
         "— both gates must pass; see docs/observability.md tuning).")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(4.0)
)

SKEW_REL_FACTOR = (
    ConfigBuilder("cyclone.telemetry.skew.relFactor")
    .doc("Relative gate for straggler detection: the lane median must "
         "also exceed relFactor x the group median, so microscopic "
         "jitter in a tight group (MAD near 0) cannot convict.")
    .check_value(lambda v: v >= 1.0, "must be >= 1.0")
    .float_conf(1.5)
)

SKEW_MIN_GAP_MS = (
    ConfigBuilder("cyclone.telemetry.skew.minGapMs")
    .doc("Absolute-gap floor for straggler detection: a lane's rolling "
         "median must exceed the group median by at least this many "
         "milliseconds (on top of the MAD and relative gates). At "
         "millisecond step times benign jitter exceeds any relative "
         "factor; below this gap, mitigation could not pay for itself.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(10.0)
)

SLO_STEP_MS = (
    ConfigBuilder("cyclone.telemetry.slo.stepMs")
    .doc("Step-duration SLO in milliseconds for collective dispatches "
         "(group collectives.step): a sample over target fires ONE "
         "latched SloBreach event + a flight-recorder dump until a "
         "sample recovers. 0 disables.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(0.0)
)

SLO_SERVING_MS = (
    ConfigBuilder("cyclone.telemetry.slo.servingMs")
    .doc("Serving-dispatch SLO in milliseconds (group serving.dispatch); "
         "same latch/dump semantics as slo.stepMs. 0 disables.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(0.0)
)

USAGE_ENABLED = (
    ConfigBuilder("cyclone.usage.enabled")
    .doc("Per-job / per-tenant usage attribution (observe/attribution.py): "
         "work dispatched inside attribution.scope(job, tenant=...) "
         "charges device-seconds, FLOPs / bytes-accessed / HBM-peak "
         "(joined from the observe.costs registry), host->device staging "
         "bytes, serving requests / dispatch-seconds / sheds and "
         "supervisor/autoscaler actions to a bounded process-global "
         "UsageLedger. Periodic UsageReport events feed the status store "
         "(/api/v1/usage, web UI, history replay), labeled Prometheus "
         "gauges, and FitProfile.job_usage; per-host ledgers ride shipped "
         "span batches so the TraceCollector merges them cross-host. Off "
         "by default; the disabled cost at every instrumentation site is "
         "one module-global read (the usage BENCH block pins it).")
    .bool_conf(False)
)

USAGE_MAX_SCOPES = (
    ConfigBuilder("cyclone.usage.maxScopes")
    .doc("UsageLedger scope-row bound: past it the oldest scope folds "
         "into the '(evicted)' row (sums still match the totals row) and "
         "its labeled gauges unregister.")
    .check_value(lambda v: v >= 2, "must be >= 2")
    .int_conf(256)
)

USAGE_MAX_MODELS = (
    ConfigBuilder("cyclone.usage.maxModels")
    .doc("Per-scope serving model-table bound; overflow models share one "
         "'(other)' bucket.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(64)
)

USAGE_REPORT_INTERVAL_MS = (
    ConfigBuilder("cyclone.usage.reportIntervalMs")
    .doc("UsageReport / TelemetryStatsUpdated posting period in "
         "milliseconds. Reports carry CUMULATIVE snapshots, so the "
         "status store folds them by replacement and a lost report "
         "costs staleness, not data.")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(2000.0)
)


DOCTOR_RECOMPILE_MIN = (
    ConfigBuilder("cyclone.doctor.recompileMin")
    .doc("Recompile-storm conviction floor for observe/diagnose.py: the "
         "total number of EXCESS compile spans (beyond the first per "
         "program-cache identity) in the analyzed window before the "
         "doctor files a recompile-storm finding. The first compile of "
         "each program is warm-up, never evidence.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(2)
)

DOCTOR_TRANSFER_STALL_FRACTION = (
    ConfigBuilder("cyclone.doctor.transferStallFraction")
    .doc("Host-transfer stall threshold: non-streaming transfer-span "
         "seconds must reach this fraction of dispatch+collective "
         "seconds before the doctor convicts (the runtime twin of "
         "JX001's per-element device_get rule). oocore.* staging spans "
         "are excluded — streaming health is the overlap rule's job.")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(0.5)
)

DOCTOR_TRANSFER_MIN_COUNT = (
    ConfigBuilder("cyclone.doctor.transferMinCount")
    .doc("Minimum non-streaming transfer spans in the window before the "
         "transfer-stall rule may fire: one big final readback is a "
         "result fetch, not a stall pattern.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(8)
)

DOCTOR_OVERLAP_MIN = (
    ConfigBuilder("cyclone.doctor.overlapMin")
    .doc("Under-lapped-streaming threshold: the stage/compute overlap "
         "fraction (same interval math as scripts/bench_oocore.py) "
         "below which the doctor flags the double buffer as not "
         "hiding staging. Mirrors the bench gate's 0.30 floor.")
    .check_value(lambda v: 0.0 <= v <= 1.0, "must be in [0, 1]")
    .float_conf(0.30)
)

DOCTOR_MIN_STREAM_SPANS = (
    ConfigBuilder("cyclone.doctor.minStreamSpans")
    .doc("Minimum oocore.stage AND oocore.shard span count before the "
         "overlap rule judges a window; tiny streams have no steady "
         "state to measure.")
    .check_value(lambda v: v >= 2, "must be >= 2")
    .int_conf(8)
)

DOCTOR_SHED_MIN = (
    ConfigBuilder("cyclone.doctor.shedMin")
    .doc("Serving-pressure conviction floor: total shed requests in the "
         "serving stats snapshot at or above this files a finding.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1)
)

DOCTOR_FALLBACK_MIN = (
    ConfigBuilder("cyclone.doctor.fallbackMin")
    .doc("Precision-envelope churn floor: precision.fallback events in "
         "the window at or above this files a finding (the fp8 "
         "envelope is re-proving itself instead of staying settled).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1)
)

DOCTOR_FLIGHT_DIAGNOSIS = (
    ConfigBuilder("cyclone.doctor.flightDiagnosis")
    .doc("Auto-attach a DiagnosisReport to every flight-recorder dump: "
         "the doctor runs over the captured ring (spans only, no live "
         "sources) so a post-mortem dump arrives pre-triaged. Failures "
         "in the doctor never break the dump itself.")
    .bool_conf(True)
)

REGRESS_WINDOW = (
    ConfigBuilder("cyclone.regress.window")
    .doc("Bench-drift window: the newest row of each metric is judged "
         "against the median+MAD of up to this many preceding "
         "comparable rows in artifacts/bench_history.jsonl.")
    .check_value(lambda v: v >= 2, "must be >= 2")
    .int_conf(5)
)

REGRESS_MAD_FACTOR = (
    ConfigBuilder("cyclone.regress.madFactor")
    .doc("Robust drift threshold: a candidate beyond "
         "median +/- max(madFactor*MAD, relTol*median) in the bad "
         "direction is a regression; beyond it in the good direction "
         "is an improvement.")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(4.0)
)

REGRESS_REL_TOL = (
    ConfigBuilder("cyclone.regress.relTol")
    .doc("Relative floor under the MAD threshold: with a near-zero MAD "
         "(identical historical runs) drift under relTol*median still "
         "passes, so the gate never flags noise-free jitter.")
    .check_value(lambda v: v > 0, "must be > 0")
    .float_conf(0.05)
)

REGRESS_MIN_RUNS = (
    ConfigBuilder("cyclone.regress.minRuns")
    .doc("Minimum comparable history rows before a metric is gated; "
         "with fewer the verdict is insufficient-history (ok, never "
         "a nonzero exit).")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(3)
)


MULTIHOST_REPLICAS = (
    ConfigBuilder("cyclone.multihost.replicas")
    .doc("Replica (DCN) rows of the hierarchical mesh. 0 (default) is "
         "auto: one replica row per process, so every cross-process "
         "collective is confined to the replica axis and the data/model "
         "axes stay on ICI (multihost/hierarchy.py). An explicit value "
         "is honoured — with a warning when rows would straddle a "
         "process boundary.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(0)
)

MULTIHOST_MODEL_PARALLELISM = (
    ConfigBuilder("cyclone.multihost.modelParallelism")
    .doc("Model (feature-TP) axis width of the hierarchical mesh; stays "
         "inside one process's ICI domain.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(1)
)

MULTIHOST_CPU_COLLECTIVES = (
    ConfigBuilder("cyclone.multihost.cpuCollectives")
    .doc("Cross-process collectives implementation for CPU-backend "
         "multihost meshes (the 2-process smoke of the DCN hop): 'gloo' "
         "(default) enables real cross-process psums on XLA:CPU; 'none' "
         "leaves stock XLA behavior (multi-process CPU programs fail at "
         "dispatch). Ignored on TPU, whose fabric needs no helper.")
    .check_value(lambda v: v in ("gloo", "none"), "must be gloo or none")
    .str_conf("gloo")
)

MULTIHOST_BARRIER_TIMEOUT_MS = (
    ConfigBuilder("cyclone.multihost.barrierTimeoutMs")
    .doc("Teardown-barrier timeout in ms: context stop on a multihost "
         "mesh syncs every process at a coordination-service barrier "
         "before disconnecting (no process tears down the backend while "
         "a peer is mid-collective); a dead peer bounds the wait here.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(10000)
)

ELASTIC_MAX_RESHAPES = (
    ConfigBuilder("cyclone.elastic.maxReshapes")
    .doc("Planned mesh-shape changes (CapacityEvents) a MeshSupervisor "
         "applies before aborting with MeshDegradedError — the elastic "
         "twin of the max_rebuilds recovery budget, kept SEPARATE so a "
         "flapping autoscaler cannot eat the budget a real failure "
         "needs. Each reshape migrates cached datasets in memory, "
         "rebuilds the mesh at the event's master URL and resumes the "
         "fit in place from live optimizer state (no checkpoint "
         "round-trip); see docs/resilience.md 'Elasticity'.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(4)
)

ELASTIC_DRAIN_WINDOW_MS = (
    ConfigBuilder("cyclone.elastic.drainWindowMs")
    .doc("Default drain window for a preemption notice that names none: "
         "the in-memory optimizer-state handoff must complete within "
         "this budget of the notice for the rebuild to resume from the "
         "drained state; past it the handoff is DISCARDED and recovery "
         "falls back to the newest verifiable checkpoint — expired "
         "state is never silently resumed.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(5000)
)

ELASTIC_SPECULATION = (
    ConfigBuilder("cyclone.elastic.speculation")
    .doc("Arm Spark-style speculative re-dispatch for lanes with latched "
         "straggler verdicts (observe/skew.py -> supervisor.stragglers())"
         ": a convicted lane's next work runs with a duplicate copy — "
         "concurrent for host-side lanes (oocore shard staging), serial "
         "on the idle mesh for SPMD fit lanes — first result wins, the "
         "duplicate dedups bitwise. Off by default: speculation spends "
         "duplicate work, exactly as the reference's "
         "spark.speculation=false default does.")
    .mutable()
    .bool_conf(False)
)

AUTOSCALE_ENABLED = (
    ConfigBuilder("cyclone.autoscale.enabled")
    .doc("Arm the autoscaler control loop (elastic/autoscale.py): "
         "context.mesh_supervisor() starts a sampler thread that feeds "
         "skew/SLO/occupancy signals through the hysteresis policy and "
         "announces CapacityEvents on the elastic channel. Off by "
         "default: the control plane is opt-in, exactly as "
         "spark.dynamicAllocation.enabled=false is.")
    .bool_conf(False)
)

AUTOSCALE_TARGET_P99_MS = (
    ConfigBuilder("cyclone.autoscale.targetP99Ms")
    .doc("Serving p99 latency target in milliseconds, judged against "
         "the serving.dispatch timer histogram each tick: sustained "
         "breach (scaleUpAfterN consecutive ticks) votes scale-up. "
         "0 disables the serving leg; training pressure (stragglers, "
         "stepMs SLO) still drives the loop.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .float_conf(0.0)
)

AUTOSCALE_SCALE_UP_AFTER = (
    ConfigBuilder("cyclone.autoscale.scaleUpAfterN")
    .doc("Hysteresis window for growth: consecutive breached ticks "
         "(serving p99 over target, latched stragglers, or step-SLO "
         "latch) before ONE scale-up decision fires. Any healthy tick "
         "resets the streak — a flapping signal never reaches a "
         "verdict.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(3)
)

AUTOSCALE_SCALE_DOWN_AFTER = (
    ConfigBuilder("cyclone.autoscale.scaleDownAfterN")
    .doc("Hysteresis window for shrink: consecutive idle ticks "
         "(occupancy below the idle fraction with no breach) before a "
         "scale-down decision. Deliberately longer than scaleUpAfterN "
         "by default: shedding capacity too eagerly is the expensive "
         "mistake.")
    .check_value(lambda v: v >= 1, "must be >= 1")
    .int_conf(6)
)

AUTOSCALE_COOLDOWN_MS = (
    ConfigBuilder("cyclone.autoscale.cooldownMs")
    .doc("Per-direction cooldown after an applied decision, in LOGICAL "
         "milliseconds (Signals.t_ms — replay-stable): the same "
         "direction is suppressed until it elapses, so a persistent "
         "breach re-decides at a bounded rate instead of storming the "
         "reshape budget.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(30000)
)

AUTOSCALE_ACQUIRE_TIMEOUT_MS = (
    ConfigBuilder("cyclone.autoscale.acquireTimeoutMs")
    .doc("Bounded deadline for the scale-up capacity acquisition "
         "(parallel/allocation.acquire_devices): past it the decision "
         "degrades to a logged no-op + CapacityAcquired(ok=False) event "
         "and the train loop never wedges waiting on capacity that is "
         "not coming.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(5000)
)

AUTOSCALE_MAX_DECISIONS = (
    ConfigBuilder("cyclone.autoscale.maxDecisions")
    .doc("Applied-decision budget for one autoscaler life, SEPARATE "
         "from cyclone.elastic.maxReshapes: an exhausted policy "
         "degrades to one latched warn-hold decision and then holds — "
         "a misbehaving controller warns, it never thrashes the mesh "
         "or eats the reshape budget a real failure needs.")
    .check_value(lambda v: v >= 0, "must be >= 0")
    .int_conf(8)
)
