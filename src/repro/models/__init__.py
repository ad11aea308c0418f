from repro.models import encdec, layers, model_zoo, moe, ssm, transformer
from repro.models.model_zoo import Model, build, init_serving_params, synthetic_batch

__all__ = [
    "Model",
    "build",
    "encdec",
    "init_serving_params",
    "layers",
    "model_zoo",
    "moe",
    "ssm",
    "synthetic_batch",
    "transformer",
]
