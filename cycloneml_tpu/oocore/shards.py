"""Host shard store: the out-of-core dataset representation.

A :class:`StreamingDataset` is what an estimator trains on when the design
matrix must never fully materialize in device memory — the analog of the
reference's disk-backed block store feeding tasks one partition at a time
(ref BlockManager / UnifiedMemoryManager spill discipline, PAPER.md layer
3c). It is a sequence of bounded npz shard files (data-tier packed X,
accumulator-tier y/w) plus the ONE-pass statistics every fit path needs
(Summarizer moments, label histogram, label moments, weight sum) —
harvested while the shards are WRITTEN, so no extra epoch is spent on
stats and no O(n) host vector survives construction.

Geometry contract: every shard is padded — at STAGE time, not on disk —
to one fixed ``(pad_rows, d)`` block (zero-weight rows, masked out of the
psums exactly like the in-core padding), so a single compiled per-shard
aggregation program serves the whole epoch and host staging peaks at
O(pad_rows · d), never O(n · d).
"""

from __future__ import annotations

import os
import tempfile
import threading
from dataclasses import dataclass
from typing import Iterable, List, Optional

import numpy as np

from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)

#: labels above this are not class indices — histogram harvesting stops
_MAX_CLASSES = 4096


@dataclass
class _Moments:
    """f64 running sums mirroring ``ml/stat/summarizer._moments`` (same
    masking: rows with w > 0 are 'present') plus the label-side sums the
    fit paths read (histogram for classifiers, y moments for regressors)."""

    d: int
    s1: np.ndarray = None
    s2: np.ndarray = None
    l1: np.ndarray = None
    nnz: np.ndarray = None
    mx: np.ndarray = None
    mn: np.ndarray = None
    w: float = 0.0
    w2: float = 0.0
    cnt: float = 0.0
    s1y: float = 0.0
    s2y: float = 0.0
    w_max: float = 0.0
    abs_all: np.ndarray = None
    histogram: Optional[np.ndarray] = None
    integral_labels: bool = True

    def __post_init__(self):
        self.s1 = np.zeros(self.d)
        self.s2 = np.zeros(self.d)
        self.l1 = np.zeros(self.d)
        self.nnz = np.zeros(self.d)
        self.mx = np.full(self.d, -np.inf)
        self.mn = np.full(self.d, np.inf)
        self.abs_all = np.zeros(self.d)
        self.histogram = np.zeros(0)

    def update(self, x: np.ndarray, y: np.ndarray, w: np.ndarray) -> None:
        # moments are taken from the DATA-TIER view of the rows (x is
        # already cast to storage width), so streamed stats match what an
        # in-core Summarizer pass over the same stored blocks computes
        x64 = np.asarray(x, dtype=np.float64)
        y64 = np.asarray(y, dtype=np.float64)
        w64 = np.asarray(w, dtype=np.float64)
        wcol = w64[:, None]
        present = w64 > 0
        self.s1 += (wcol * x64).sum(axis=0)
        self.s2 += (wcol * x64 * x64).sum(axis=0)
        self.l1 += (wcol * np.abs(x64)).sum(axis=0)
        self.w += float(w64.sum())
        self.w2 += float((w64 * w64).sum())
        self.cnt += float(present.sum())
        if present.any():
            xp = x64[present]
            self.nnz += (xp != 0).sum(axis=0)
            self.mx = np.maximum(self.mx, xp.max(axis=0))
            self.mn = np.minimum(self.mn, xp.min(axis=0))
        if x64.shape[0]:
            # ALL-row absmax (zero-weight rows included): the fp8 set
            # scale must dominate every stored value — an out-of-range
            # code is NaN, and 0 · NaN would still poison the psum
            self.abs_all = np.maximum(self.abs_all, np.abs(x64).max(axis=0))
        self.s1y += float((w64 * y64).sum())
        self.s2y += float((w64 * y64 * y64).sum())
        if w64.size:
            # max instance weight feeds the fp8 envelope probe's
            # multiplier-overflow heuristic (instance.fp8_probe_ok)
            self.w_max = max(self.w_max, float(w64.max()))
        if self.integral_labels:
            yp = y64[present]
            if yp.size and (np.any(yp != np.round(yp)) or yp.min() < 0
                            or yp.max() >= _MAX_CLASSES):
                self.integral_labels = False
            elif yp.size:
                hist = np.bincount(yp.astype(np.int64),
                                   weights=w64[present],
                                   minlength=len(self.histogram))
                if len(hist) > len(self.histogram):
                    self.histogram = np.pad(
                        self.histogram, (0, len(hist) - len(self.histogram)))
                self.histogram = self.histogram + hist


@dataclass
class _Shard:
    path: str
    rows: int


class StreamingDataset:
    """Disk-backed shard sequence + one-pass fit statistics.

    Quacks like the corner of :class:`InstanceDataset` the dense fit paths
    touch (``n_rows`` / ``n_features`` / ``shape`` / ``ctx`` /
    ``to_instance_dataset`` returning self), so ``est.fit(streaming_ds)``
    routes through the normal estimator entry and ``_fit_dataset``
    dispatches on the type. Shard files are OWNED: removed on
    :meth:`close` or GC.
    """

    def __init__(self, ctx, shards: List[_Shard], n_features: int,
                 pad_rows: int, moments: _Moments, spill_dir: str,
                 owns_dir: bool, x_dtype=None,
                 x_scale: Optional[np.ndarray] = None):
        self.ctx = ctx
        self._shards = shards
        self.n_features = int(n_features)
        self.n_rows = int(sum(s.rows for s in shards))
        self.pad_rows = int(pad_rows)
        self._moments = moments
        self._dir = spill_dir
        self._owns_dir = owns_dir
        # the STREAM dtype: what load_shard/ShardStream stage (fp8 shard
        # sets stage 1-byte e4m3 codes); per-column dequant scale rides
        # alongside, folded into the aggregator read as in-core fp8 fits do
        self.x_dtype = np.dtype(x_dtype) if x_dtype is not None \
            else np.dtype(np.float64)
        self.x_scale: Optional[np.ndarray] = (
            np.asarray(x_scale, dtype=np.float64)
            if x_scale is not None else None)
        self._closed = False
        self._close_lock = threading.Lock()

    # -- construction ----------------------------------------------------------
    @classmethod
    def from_chunks(cls, ctx, chunks: Iterable, n_features: int,
                    shard_rows: Optional[int] = None,
                    spill_dir: Optional[str] = None,
                    stream_dtype: Optional[str] = None,
                    x_scale: Optional[np.ndarray] = None
                    ) -> "StreamingDataset":
        """Build from an iterator of ``(x, y_or_None, w_or_None)`` host
        chunks — the ``dataset/io.py`` chunked-reader contract — WITHOUT
        ever holding more than one shard of rows host-side. Chunks are
        re-blocked to ``cyclone.oocore.shardRows`` boundaries; X is cast to
        the stream tier before it is written (bf16 shards carry half the
        bytes of f32, fp8 shards half again, so the host→device stream —
        the out-of-core fit's bandwidth bill — halves per rung,
        docs/mixed-precision.md).

        ``stream_dtype`` overrides ``cyclone.oocore.streamDtype`` for this
        build. When the resolved rung is fp8, the write pass stays one
        rung wider (the set-level absmax is unknown mid-stream) and a
        FINALIZE pass requantizes every shard with ONE set-level
        per-column scale — decided by the materialization-time envelope
        probe over the write-pass moments, per shard SET, not per shard:
        one geometry, one dequant fold, one compiled program per epoch.
        A probe refusal stays at the wider rung, surfaced as a
        ``PrecisionFallback`` event — automatic and visible, never silent.

        ``x_scale`` is the PRE-QUANTIZED spill contract
        (:meth:`from_dataset` over an fp8 in-core dataset): chunks carry
        e4m3 codes whose real value is ``code * x_scale``; they are
        written through unchanged, the moments are harvested from the
        dequantized VIEW (fit statistics are about values, not codes),
        and the probe is skipped — the in-core rail already ran it."""
        from cycloneml_tpu.conf import OOCORE_DIR, OOCORE_SHARD_ROWS
        from cycloneml_tpu.dataset.instance import compute_dtype
        conf = getattr(ctx, "conf", None)
        if shard_rows is None:
            shard_rows = int(conf.get(OOCORE_SHARD_ROWS)) if conf is not None \
                else 65536
        shard_rows = max(int(shard_rows), 1)
        base = (conf.get(OOCORE_DIR) if conf is not None else "") or ""
        # only a dir we minted ourselves is removed on close; a
        # caller-provided directory is theirs
        owns_dir = spill_dir is None
        spill_dir = spill_dir or tempfile.mkdtemp(
            prefix="oocore-", dir=base or None)
        os.makedirs(spill_dir, exist_ok=True)

        if x_scale is not None:
            import ml_dtypes
            xdt = np.dtype(ml_dtypes.float8_e4m3fn)
            fp8_candidate = False
            x_scale = np.asarray(x_scale, dtype=np.float64)
        else:
            xdt, fp8_candidate = _resolve_stream_dtype(conf, stream_dtype)
        ydt = np.dtype(compute_dtype())
        moments = _Moments(int(n_features))
        shards: List[_Shard] = []
        carry: List[tuple] = []   # [(x, y, w)] pieces, < shard_rows total
        carry_rows = 0

        def flush(pieces, rows):
            xs = np.concatenate([p[0] for p in pieces]) if len(pieces) > 1 \
                else pieces[0][0]
            ys = np.concatenate([p[1] for p in pieces]) if len(pieces) > 1 \
                else pieces[0][1]
            ws = np.concatenate([p[2] for p in pieces]) if len(pieces) > 1 \
                else pieces[0][2]
            path = os.path.join(spill_dir, f"shard-{len(shards):06d}.npz")
            from cycloneml_tpu.dataset.dataset import _npz_pack
            x_packed, x_dtype = _npz_pack(xs)
            np.savez(path, x=x_packed, x_dtype=x_dtype, y=ys, w=ws)
            shards.append(_Shard(path, rows))
            if x_scale is not None:
                # codes are not values: stats come from the dequant view
                xs = np.asarray(xs, dtype=np.float64) * x_scale[None, :]
            moments.update(xs, ys, ws)

        for ci, (cx, cy, cw) in enumerate(chunks):
            cx = np.ascontiguousarray(cx, dtype=xdt)
            m = cx.shape[0]
            if cx.ndim != 2 or cx.shape[1] != n_features:
                raise ValueError(f"chunk {ci} has shape {cx.shape}, "
                                 f"expected (rows, {n_features})")
            cy = (np.zeros(m, dtype=ydt) if cy is None
                  else np.asarray(cy, dtype=ydt))
            cw = (np.ones(m, dtype=ydt) if cw is None
                  else np.asarray(cw, dtype=ydt))
            if len(cy) != m or len(cw) != m:
                raise ValueError(
                    f"chunk {ci}: y/w lengths ({len(cy)}/{len(cw)}) != "
                    f"x rows ({m})")
            lo = 0
            while lo < m:
                take = min(m - lo, shard_rows - carry_rows)
                carry.append((cx[lo:lo + take], cy[lo:lo + take],
                              cw[lo:lo + take]))
                carry_rows += take
                lo += take
                if carry_rows >= shard_rows:
                    flush(carry, carry_rows)
                    carry, carry_rows = [], 0
        if carry_rows:
            flush(carry, carry_rows)
        if not shards:
            raise ValueError("empty chunk stream: nothing to shard")

        pad_rows = _pad_geometry(ctx, max(s.rows for s in shards))
        sds = cls(ctx, shards, n_features, pad_rows, moments, spill_dir,
                  owns_dir, x_dtype=xdt, x_scale=x_scale)
        if fp8_candidate:
            _finalize_fp8(sds)
        return sds

    @classmethod
    def from_dataset(cls, ds, shard_rows: Optional[int] = None,
                     spill_dir: Optional[str] = None) -> "StreamingDataset":
        """Spill an in-core :class:`InstanceDataset` into a shard set (the
        budget-guard degradation path: the DATA already fits — it is the
        fit PROGRAM whose predicted peak HBM does not). Rows are pulled in
        bounded per-shard slices — O(shard) host staging, the graftlint
        JX018 pass idiom — with interleaved padding rows dropped via the
        dataset's own valid mask.

        An fp8 in-core dataset spills its 1-byte e4m3 CODES directly,
        carrying the per-column dequant scale onto the shard set — the
        in-core envelope probe already admitted this data to the fp8
        rung, so the stream keeps it (and keeps the halved byte bill).
        Only a ``streamDtype=bfloat16`` pin forces the codes back up,
        visibly (``PrecisionFallback``)."""
        from cycloneml_tpu.conf import OOCORE_SHARD_ROWS
        conf = getattr(ds.ctx, "conf", None)
        x_scale = getattr(ds, "x_scale", None)
        if x_scale is not None and _stream_intent(conf) == "bfloat16":
            # the stream is PINNED to the bf16 rung: the codes must leave
            # the fp8 tier before sharding — visibly, never silently
            from cycloneml_tpu.dataset.dataset import fp8_fallback
            ds = fp8_fallback(
                ds, "StreamingDataset.from_dataset",
                "cyclone.oocore.streamDtype=bfloat16 pins the stream to "
                "the bf16 rung")
            x_scale = None
        if shard_rows is None:
            shard_rows = int(conf.get(OOCORE_SHARD_ROWS)) if conf is not None \
                else 65536
        shard_rows = max(int(shard_rows), 1)

        n_pad = int(ds.x.shape[0])
        mask = ds._valid_mask
        y_host = ds.y_host()
        w_host = ds.w_host()

        def chunks():
            for lo in range(0, n_pad, shard_rows):
                hi = lo + min(shard_rows, n_pad - lo)
                xs = np.asarray(ds.x[lo:hi])
                ys = np.asarray(y_host[lo:hi], dtype=np.float64)
                ws = np.asarray(w_host[lo:hi], dtype=np.float64)
                if mask is not None:
                    keep = mask[lo:hi]
                else:
                    keep = np.zeros(hi - lo, dtype=bool)
                    keep[: max(0, min(ds.n_rows, hi) - lo)] = True
                if not keep.all():
                    xs, ys, ws = xs[keep], ys[keep], ws[keep]
                if len(ys):
                    yield xs, ys, ws

        return cls.from_chunks(ds.ctx, chunks(), ds.n_features,
                               shard_rows=shard_rows, spill_dir=spill_dir,
                               x_scale=x_scale)

    # -- InstanceDataset-shaped surface ---------------------------------------
    @property
    def shape(self):
        return (self.n_rows, self.n_features)

    @property
    def n_shards(self) -> int:
        return len(self._shards)

    def to_instance_dataset(self, features_col=None, label_col=None,
                            weight_col=None, dtype=None,
                            fp8_capable: bool = False) -> "StreamingDataset":
        """Estimator bridge parity with :class:`InstanceDataset`: a
        StreamingDataset is already placed (on disk); column/dtype
        concepts do not apply. The fp8 opt-in DOES: an fp8 shard set
        handed to a consumer that has not declared quantized-storage
        capability re-spills at the bf16 rung (PrecisionFallback event) —
        an estimator that would read raw e4m3 codes as values must never
        see them, the same contract as ``instance.data_dtype``."""
        if self.x_scale is not None and not fp8_capable:
            _precision_fallback_event(
                self.ctx, "StreamingDataset.to_instance_dataset",
                "the consumer is not fp8-capable: e4m3 codes would be "
                "read as values", str(self.x_dtype), "bfloat16")
            scale = self.x_scale

            def chunks():
                for i in range(self.n_shards):
                    x, y, w = self.load_shard(i)
                    yield (np.asarray(x, dtype=np.float64) * scale[None, :],
                           y, w)

            return StreamingDataset.from_chunks(
                self.ctx, chunks(), self.n_features,
                shard_rows=max(s.rows for s in self._shards),
                stream_dtype="bfloat16")
        return self

    # -- one-pass statistics ---------------------------------------------------
    @property
    def weight_sum(self) -> float:
        return self._moments.w

    def summary(self):
        """Summarizer-equivalent :class:`SummaryStats` from the write-pass
        moments — the streamed fit never pays a stats epoch."""
        from cycloneml_tpu.ml.stat.summarizer import SummaryStats
        m = self._moments
        mean = m.s1 / m.w if m.w > 0 else np.zeros(self.n_features)
        denom = m.w - m.w2 / m.w if m.w > 0 else 0.0
        if denom > 0:
            variance = np.maximum((m.s2 - m.w * mean * mean) / denom, 0.0)
        else:
            variance = np.zeros_like(mean)
        return SummaryStats(
            mean=mean, variance=variance, count=int(round(m.cnt)),
            num_nonzeros=m.nnz.copy(), max=m.mx.copy(), min=m.mn.copy(),
            norm_l1=m.l1.copy(), norm_l2=np.sqrt(np.maximum(m.s2, 0.0)),
            sum=m.s1.copy(), weight_sum=m.w,
            label_sum=m.s1y, label_sq_sum=m.s2y, weight_sq_sum=m.w2)

    def label_histogram(self) -> np.ndarray:
        """Weighted class histogram (f64) when labels are class indices;
        raises for non-integral labels (regression datasets)."""
        if not self._moments.integral_labels:
            raise ValueError(
                "labels are not class indices; streamed classification "
                "requires integral labels in [0, 4096)")
        return self._moments.histogram.copy()

    @property
    def num_classes(self) -> int:
        return max(len(self._moments.histogram), 2) \
            if self._moments.integral_labels else 0

    # -- shard access (the stream's supplier) ---------------------------------
    def load_shard(self, i: int):
        """Host arrays of shard ``i`` (unpadded; X at data-tier width)."""
        from cycloneml_tpu.dataset.dataset import _npz_unpack
        s = self._shards[i]
        z = np.load(s.path)
        x = _npz_unpack(z["x"], z.get("x_dtype", ""))
        return x, z["y"], z["w"]

    def shard_nbytes(self, i: int) -> int:
        s = self._shards[i]
        try:
            return os.path.getsize(s.path)
        except OSError:
            return 0

    # -- lifecycle -------------------------------------------------------------
    def close(self) -> None:
        # latch under the lock: explicit close races __del__ (GC thread),
        # and both passing the check would double-unlink the spill files
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        for s in self._shards:
            try:
                os.unlink(s.path)
            except OSError:
                pass
        if self._owns_dir:
            try:
                os.rmdir(self._dir)
            except OSError:
                pass

    def __del__(self):  # dropped shard sets must not leak the spill dir
        try:
            self.close()
        except Exception:
            pass


def _pad_geometry(ctx, max_shard_rows: int) -> int:
    """Padded rows per staged shard: the max shard rounded up to a
    sublane-friendly multiple of the mesh's data parallelism, so
    ``device_put_sharded_rows`` splits every staged block evenly and one
    compiled program serves every shard."""
    rt = ctx.mesh_runtime
    unit = 8 * int(rt.data_parallelism)
    return ((max(int(max_shard_rows), 1) + unit - 1) // unit) * unit


def _stream_intent(conf, override: Optional[str] = None) -> str:
    """The configured stream rung: 'auto' | 'bfloat16' | 'float8'."""
    if override is not None:
        return str(override)
    if conf is None:
        return "auto"
    from cycloneml_tpu.conf import OOCORE_STREAM_DTYPE
    return str(conf.get(OOCORE_STREAM_DTYPE))


def _resolve_stream_dtype(conf, override: Optional[str] = None):
    """Resolve ``cyclone.oocore.streamDtype`` to ``(write_dtype,
    fp8_candidate)`` for a fresh spill. ``write_dtype`` is what the WRITE
    pass stores — one rung wider than fp8 when fp8 is the candidate,
    because the set-level scale does not exist until every row has passed
    through the moments; the finalize pass requantizes (or refuses, per
    the envelope probe). 'auto' follows ``cyclone.data.dtype`` including
    its fp8 tiers — the stream is an fp8-capable consumer: the dequant
    scale folds into the aggregator read exactly as the in-core fit's."""
    from cycloneml_tpu.dataset.instance import (compute_dtype, data_dtype,
                                                is_fp8_dtype)
    intent = _stream_intent(conf, override)
    if intent == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16), False
    if intent == "float8":
        fp8 = True
    else:  # auto: follow the data tier, fp8-capable
        fp8 = is_fp8_dtype(data_dtype(conf, fp8_capable=True))
        if not fp8:
            return np.dtype(data_dtype(conf)), False
    # fp8 candidate: write one rung wider (f64 under the x64 parity
    # config so requantization sees pre-tier values, bf16 otherwise)
    if compute_dtype() is np.float64:
        return np.dtype(np.float64), fp8
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16), fp8


def _finalize_fp8(sds: StreamingDataset) -> None:
    """The materialization-time envelope probe + set-level requantize.

    Decides fp8-vs-bf16 for the shard SET, not per shard: ONE per-column
    scale (``absmax / FP8_MAX`` from the write-pass moments) serves every
    shard, so one geometry and one compiled program serve the epoch and
    the dequant fold is a single replicated (d,) vector — exactly the
    in-core fp8 fit's arrangement. The probe runs on the same write-pass
    moments (``instance.fp8_probe_ok``: scale-spread + multiplier
    overflow, zero extra data passes); a refusal keeps the shards at the
    write rung and posts ``PrecisionFallback`` — automatic and visible.
    On success each shard is rewritten in place, one shard resident at a
    time (O(shard) host peak, the JX018 bound)."""
    from cycloneml_tpu.dataset.dataset import _npz_pack
    from cycloneml_tpu.dataset.instance import (FP8_MAX, fp8_probe_ok,
                                                quantize_fp8)
    m = sds._moments
    absmax = np.maximum(np.abs(m.mx), np.abs(m.mn))
    absmax = np.where(np.isfinite(absmax), absmax, 0.0)
    stats = sds.summary()
    std = np.sqrt(np.asarray(stats.variance, dtype=np.float64))
    probe_ratio = np.where(std > 0, absmax / np.where(std > 0, std, 1.0),
                           0.0)
    reason = fp8_probe_ok(stats, w_max=m.w_max or None,
                          probe_ratio=probe_ratio)
    if reason is not None:
        _precision_fallback_event(
            sds.ctx, "StreamingDataset", reason, "float8_e4m3fn",
            str(sds.x_dtype))
        return
    scale = np.where(m.abs_all > 0, m.abs_all / FP8_MAX, 1.0)
    # re-harvest the moments from the DEQUANTIZED view in the same pass:
    # fit statistics must describe the values the fit will actually read
    # (codes ∘ scale), exactly as the in-core Summarizer sees a quantized
    # dataset — write-rung stats would hand the optimizer a subtly
    # different standardization than the data it streams
    requant = _Moments(sds.n_features)
    for i, s in enumerate(sds._shards):
        x, y, w = sds.load_shard(i)
        x8, _, _ = quantize_fp8(x, scale=scale)
        x_packed, x_dtype = _npz_pack(x8)
        np.savez(s.path, x=x_packed, x_dtype=x_dtype, y=y, w=w)
        requant.update(np.asarray(x8, dtype=np.float64) * scale[None, :],
                       y, w)
    sds._moments = requant
    sds.x_scale = scale
    sds.x_dtype = np.dtype(x8.dtype)
    logger.info(
        "oocore: shard set requantized to float8_e4m3fn (%d shards, "
        "set-level per-column scale)", sds.n_shards)


def _precision_fallback_event(ctx, estimator: str, reason: str,
                              from_dtype: str, to_dtype: str) -> None:
    """Surface a streaming-tier precision decision the way the in-core
    ``dataset.fp8_fallback`` does — warning log, ``precision.fallback``
    tracing instant (the ``FitProfile.fp8_fallbacks`` counter), and a
    ``PrecisionFallback`` event on the context bus — without requiring an
    :class:`InstanceDataset` to dequantize."""
    from cycloneml_tpu.observe import tracing
    logger.warning("%s: falling back from %s to %s storage — %s",
                   estimator, from_dtype, to_dtype, reason)
    tracing.instant("precision.fallback", estimator=estimator,
                    reason=reason, from_dtype=from_dtype)
    bus = getattr(ctx, "listener_bus", None)
    if bus is not None:
        from cycloneml_tpu.util.events import PrecisionFallback
        try:
            bus.post(PrecisionFallback(estimator=estimator,
                                       from_dtype=from_dtype,
                                       to_dtype=to_dtype, reason=reason))
        except Exception:
            pass  # a stopped bus must not fail the fit
