from photon_ml_tpu.evaluation.metrics import (  # noqa: F401
    auc_roc,
    auc_pr,
    rmse,
    logistic_loss_metric,
    poisson_loss_metric,
    squared_loss_metric,
    smoothed_hinge_loss_metric,
    precision_at_k,
)
from photon_ml_tpu.evaluation.evaluator import (  # noqa: F401
    Evaluator,
    EvaluatorType,
    EvaluationSuite,
    EvaluationResults,
    GroupLayout,
    make_evaluator,
    grouped_evaluate,
)
