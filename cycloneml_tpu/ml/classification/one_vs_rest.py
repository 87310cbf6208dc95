"""One-vs-rest multiclass reduction.

Re-design of the reference (ref: ml/classification/OneVsRest.scala — fits
one binary copy of the base classifier per class over relabeled data, with
a ``parallelism`` thread pool; the model picks the class whose binary
margin is largest).

``parallelism > 1`` routes through the STACKED fit engine when the base
classifier supports it (``fit_stacked``): the K binary fits share one
design matrix, so they run as ONE gang-scheduled SPMD program — one read
of X an evaluation for all K models, model j's label ``1[y == j]`` made
inside the sweep from the dataset's own label vector (no relabelled copy
exists), one trace + compile amortized over all K models, one psum per step
carrying K gradients, per-model convergence masks. The serial loop's
relabel is a host-side column swap (a frame) or a derived label vector (a
device-resident dataset). The reference's thread
pool (and this repo's pre-stacking port of it) dispatched K concurrent
SPMD programs onto the shared mesh and deadlocked XLA's collective
rendezvous (graftlint JX007 now mechanizes that hazard); the serial loop
remains as the fallback for classifiers/configs the stacked engine does
not cover. See docs/multi-model.md.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from cycloneml_tpu.dataset.frame import MLFrame
from cycloneml_tpu.ml.base import ClassificationModel, Estimator, Model
from cycloneml_tpu.ml.param import ParamValidators as V
from cycloneml_tpu.ml.shared import (
    HasFeaturesCol, HasLabelCol, HasPredictionCol, HasRawPredictionCol,
    HasWeightCol,
)
from cycloneml_tpu.ml.util_io import (
    MLReadable, MLWritable, load_pipeline_stages, save_pipeline_stages,
)
from cycloneml_tpu.util.logging import get_logger

logger = get_logger(__name__)


class _OVRParams(HasFeaturesCol, HasLabelCol, HasPredictionCol,
                 HasRawPredictionCol, HasWeightCol):
    def _declare_ovr_params(self):
        self._p_features_col()
        self._p_label_col()
        self._p_prediction_col()
        self._p_raw_prediction_col()
        self._p_weight_col()
        self.parallelism = self._param(
            "parallelism", "max concurrent binary fits (>= 1)",
            V.gt_eq(1), default=1)


class OneVsRest(Estimator, _OVRParams, MLWritable, MLReadable):
    def __init__(self, classifier: Optional[Estimator] = None, uid=None,
                 **kwargs):
        super().__init__(uid)
        self._declare_ovr_params()
        self.classifier = classifier
        for k, v in kwargs.items():
            self.set(k, v)

    def set_classifier(self, clf: Estimator) -> "OneVsRest":
        self.classifier = clf
        return self

    def set_parallelism(self, v):
        return self.set("parallelism", v)

    def _fit(self, frame) -> "OneVsRestModel":
        """``frame`` is an ``MLFrame`` or a device-resident
        ``InstanceDataset`` whose labels are class indices; a frame builds
        (and caches) its dataset and takes the same path."""
        if self.classifier is None:
            raise ValueError("classifier must be set")
        from cycloneml_tpu.mesh import safe_fit_parallelism
        requested = self.get("parallelism")
        clf = self._configured(self.classifier.copy())
        if hasattr(frame, "label_histogram"):
            # a dataset: the class count is its cached label histogram's,
            # no pass over the labels a fit
            num_classes = len(frame.label_histogram())
        else:
            num_classes = int(np.asarray(frame[self.get("labelCol")]).max()) + 1
        stackable = (requested > 1 and num_classes > 1
                     and hasattr(clf, "fit_stacked")
                     and clf.can_fit_stacked()
                     and hasattr(frame, "to_instance_dataset"))
        if stackable:
            effective = safe_fit_parallelism(requested,
                                             stacked_width=num_classes)
            logger.info(
                "OneVsRest: fitting %d binary models as ONE stacked SPMD "
                "program (effective parallelism %d)", num_classes, effective)
            clf.set("labelCol", self.get("labelCol"))
            ds = frame.to_instance_dataset(
                clf.get("featuresCol"), clf.get("labelCol"),
                clf.get("weightCol") or None, fp8_capable=True)
            # model j fits 1[y == j], made inside the stacked sweep from
            # the label vector the dataset (or, streamed, each staged shard)
            # holds: no label matrix, no upload a fit
            models = clf.fit_stacked(ds, num_classes=num_classes)
        else:
            # serial fallback: SPMD fits stay on this thread (a >1 thread
            # pool deadlocks the shared mesh — mesh.safe_fit_parallelism);
            # relabels are one TRANSIENT vector per class (a full (n, K)
            # matrix would sit in memory for all K sequential fits for no
            # reader)
            safe_fit_parallelism(requested)
            models = []
            for c in range(num_classes):
                one = self._configured(self.classifier.copy())
                one.set("labelCol", "_ovr_label")
                models.append(one.fit(self._relabelled(frame, c)))

        model = OneVsRestModel(models, uid=self.uid)
        self._copy_values(model)
        model._set_parent(self)
        model.summary = OneVsRestSummary.of(models)
        return model

    def _configured(self, clf):
        clf.set("featuresCol", self.get("featuresCol"))
        wc = self.get("weightCol")
        if wc and "weightCol" in clf._params:
            clf.set("weightCol", wc)
        return clf

    def _relabelled(self, frame, c: int):
        """``frame`` with the binary label ``1[label == c]``: a column
        ``_ovr_label`` of a frame, the label vector of a dataset (X and the
        weights shared, not copied)."""
        from cycloneml_tpu.dataset.instance import compute_dtype
        if hasattr(frame, "with_column"):
            y = np.asarray(frame[self.get("labelCol")])
            return frame.with_column("_ovr_label",
                                     (y == c).astype(compute_dtype()))
        sub = frame.derive(y=(frame.y == c).astype(frame.y.dtype))
        return sub.attach_host_labels(
            (frame.y_host() == c).astype(np.float64), frame.w_host())

    def copy(self, extra=None) -> "OneVsRest":
        that = super().copy(extra)
        that.classifier = self.classifier.copy() if self.classifier else None
        return that

    def _save_data(self, path: str) -> None:
        save_pipeline_stages([self.classifier], path)

    def _load_data(self, path: str, meta) -> None:
        self.classifier = load_pipeline_stages(path)[0]


class OneVsRestSummary:
    """What a ``OneVsRest`` fit cost, from its binary models' training
    summaries (MLlib's ``OneVsRestModel`` has no summary: an addition).

    ``iterations`` / ``evals`` are per model. ``total_evals`` counts sweeps
    of X: a stacked fit's shared evaluations, each of which served every
    model (so ``sum(evals) <= num_classes * total_evals``, the difference
    the lane-evaluations computed for models that had already stopped), a
    serial fit's sum over its models. ``orientation`` / ``pieces`` are the
    fused stacked sweep's tiling and bf16 pieces a product (None where the
    XLA aggregator ran, as on the host platform)."""

    @classmethod
    def of(cls, models) -> Optional["OneVsRestSummary"]:
        """The summary of ``models``, or None where a base classifier's
        models carry no optimiser counts to make one from."""
        summaries = [getattr(m, "summary", None) for m in models]
        if not summaries or not all(
                hasattr(s, "total_evals") and hasattr(s, "stacked_evals")
                for s in summaries):
            return None
        return cls(summaries)

    def __init__(self, summaries):
        first = summaries[0]
        self.num_classes = len(summaries)
        self.iterations = [int(s.total_iterations) for s in summaries]
        self.evals = [int(s.total_evals) for s in summaries]
        self.n_models = int(first.n_models)
        stacked = self.n_models > 1
        self.total_evals = int(first.stacked_evals) if stacked \
            else sum(self.evals)
        self.total_dispatches = int(first.total_dispatches) if stacked \
            else sum(int(s.total_dispatches) for s in summaries)
        self.orientation = first.orientation
        self.pieces = first.pieces
        self.objectives = [float(s.objective_history[-1])
                           for s in summaries]


class OneVsRestModel(Model, _OVRParams, MLWritable, MLReadable):
    def __init__(self, models: Optional[List[ClassificationModel]] = None,
                 uid=None):
        super().__init__(uid)
        self._declare_ovr_params()
        self.models = list(models or [])
        #: set by ``OneVsRest.fit`` (a loaded model has none)
        self.summary: Optional[OneVsRestSummary] = None

    @property
    def num_classes(self) -> int:
        return len(self.models)

    def _transform(self, frame: MLFrame) -> MLFrame:
        x = frame[self.get("featuresCol")]
        if x.ndim == 1:
            x = x[:, None]
        # margin of the positive class from each binary model
        margins = np.stack(
            [m._raw_prediction(x)[:, 1] for m in self.models], axis=1)
        out = frame
        if self.get("rawPredictionCol"):
            out = out.with_column(self.get("rawPredictionCol"), margins)
        out = out.with_column(self.get("predictionCol"),
                              margins.argmax(1).astype(np.float64))
        return out

    def copy(self, extra=None) -> "OneVsRestModel":
        that = super().copy(extra)
        that.models = [m.copy() for m in self.models]
        return that

    def _save_data(self, path: str) -> None:
        save_pipeline_stages(self.models, path)

    def _load_data(self, path: str, meta) -> None:
        self.models = load_pipeline_stages(path)
