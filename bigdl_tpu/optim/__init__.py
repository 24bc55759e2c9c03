"""bigdl_tpu.optim — optimizers, triggers, validation (reference: ``bigdl/optim``)."""

from bigdl_tpu.optim.methods import (  # noqa: F401
    OptimMethod, SGD, Adam, AdamW, Adagrad, Adadelta, Adamax, RMSprop, Ftrl,
    LBFGS)
from bigdl_tpu.optim.schedules import (  # noqa: F401
    LearningRateSchedule, Default, Step, MultiStep, EpochStep, EpochDecay,
    Poly, Exponential, NaturalExp, EpochSchedule, Regime, Plateau, Warmup,
    SequentialSchedule)
from bigdl_tpu.optim.trigger import Trigger  # noqa: F401
from bigdl_tpu.optim.validation import (  # noqa: F401
    ValidationMethod, Top1Accuracy, Top5Accuracy, Loss, MAE, TreeNNAccuracy,
    AccuracyResult, LossResult)
from bigdl_tpu.optim.regularizer import (  # noqa: F401
    Regularizer, L1Regularizer, L2Regularizer, L1L2Regularizer)
from bigdl_tpu.optim.optimizer import (  # noqa: F401
    Optimizer, LocalOptimizer)
from bigdl_tpu.optim.evaluator import (  # noqa: F401
    DistriPredictor, DistriValidator, Evaluator, LocalValidator,
    Predictor, Validator)
from bigdl_tpu.optim.prediction_service import (  # noqa: F401
    PredictionService, predict_image, serialize_activity,
    deserialize_activity)
from bigdl_tpu.utils import profiling as _profiling

_profiling.install_trace_annotator()   # leaf spans enter the profiler's trace
