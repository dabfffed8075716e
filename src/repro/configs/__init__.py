from repro.configs.base import ModelConfig, get_config, all_configs, ARCH_IDS

__all__ = ["ModelConfig", "get_config", "all_configs", "ARCH_IDS"]
