from repro.configs.base import (ConsistencySpec, FrontendConfig, InputShape,
                                MLAConfig, ModelConfig, MoEConfig,
                                RecurrentConfig, TrainConfig)
from repro.configs.registry import ARCHS, get_config, reduced_config

__all__ = [
    "ARCHS", "ConsistencySpec", "FrontendConfig", "InputShape", "MLAConfig",
    "ModelConfig", "MoEConfig", "RecurrentConfig", "TrainConfig",
    "get_config", "reduced_config",
]
