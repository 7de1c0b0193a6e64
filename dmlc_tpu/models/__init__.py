"""Demo learner families on top of the ingest + collective stack.

The reference is a backbone library, not a model zoo — its downstream
consumers (xgboost/rabit/mxnet) supply the learners. The BASELINE north star
for this rebuild names one concrete end-to-end model — LibSVM allreduce-SGD —
so this package ships that learner family TPU-natively:

- ``linear``: logistic / squared / hinge linear models, dense or sparse-CSR
  batches, data-parallel psum gradient sync over a mesh axis
- ``fm``: factorization machines (the libfm format's model family), embedding
  table sharded or replicated, same segment-sum sparse kernels
  (``AdaptiveFMLearner``: difacto's memory-adaptive FM, factors only for
  the ids seen more than ``v_threshold`` times, in a slot table)
- ``ffm``: field-aware factorization machines (libffm's model and
  AdaGrad), an entry's field taken from its id's range; the FM's step
  head, chunk loops and stateful-update skeleton at another width
- ``dlrm``: a dense net over the table's rows (DLRM: two MLPs and a dot
  interaction, plain SGD on the dense parameters and on the rows a batch
  names), on the FM's skeleton. NOT imported here: ``from
  dmlc_tpu.models.dlrm import DLRMLearner`` by who asks for it, so that
  every other learner's process imports what it did
- ``gbdt``: histogram gradient-boosted trees — the xgboost-over-rabit
  workload the reference backbone was built for, with per-level histogram
  psum standing in for rabit's allreduce
- ``fitloop``: the one fit loop linear and FM run (``fit_feed``,
  ``fit_uri``; what a learner supplies to it is ``FeedLearner``), its
  helpers (``EpochMetrics``, ``step_batch``) and the epoch boundary all
  three families share (``FitLoopObs``)
"""

from dmlc_tpu.models.fitloop import (
    EpochMetrics,
    FeedLearner,
    FitLoopObs,
    step_batch,
)

from dmlc_tpu.models.linear import (
    LINEAR_PARTITION_RULES,
    LINEAR_MP_PARTITION_RULES,
    LinearModelParam,
    LinearLearner,
    init_linear_params,
    make_hostsync_train_step,
    make_linear_train_step,
    linear_predict_dense,
)
from dmlc_tpu.models.fm import (
    AdaptiveFMLearner,
    AdaptiveFMParam,
    AdaptiveTables,
    FM_FACTOR_PARTITION_RULES,
    FM_PARTITION_RULES,
    FMParam,
    FMLearner,
    FtrlAdagrad,
    PackedTables,
    init_fm_params,
    make_fm_train_step,
)
from dmlc_tpu.models.ffm import (
    FFM_FACTOR_PARTITION_RULES,
    FFMParam,
    FFMLearner,
    init_ffm_params,
    make_ffm_train_step,
)
from dmlc_tpu.models.gbdt import (
    GBDTLearner,
    GBDTParam,
    apply_bins,
    fit_bins,
    make_forest_builder,
    make_tree_builder,
    predict_trees,
)

__all__ = [
    "EpochMetrics",
    "FeedLearner",
    "FitLoopObs",
    "step_batch",
    "LINEAR_PARTITION_RULES",
    "LINEAR_MP_PARTITION_RULES",
    "LinearModelParam",
    "LinearLearner",
    "init_linear_params",
    "make_hostsync_train_step",
    "make_linear_train_step",
    "linear_predict_dense",
    "AdaptiveFMLearner",
    "AdaptiveFMParam",
    "AdaptiveTables",
    "FM_FACTOR_PARTITION_RULES",
    "FM_PARTITION_RULES",
    "FMParam",
    "FMLearner",
    "FtrlAdagrad",
    "PackedTables",
    "init_fm_params",
    "make_fm_train_step",
    "FFM_FACTOR_PARTITION_RULES",
    "FFMParam",
    "FFMLearner",
    "init_ffm_params",
    "make_ffm_train_step",
    "GBDTLearner",
    "GBDTParam",
    "apply_bins",
    "fit_bins",
    "make_forest_builder",
    "make_tree_builder",
    "predict_trees",
]
