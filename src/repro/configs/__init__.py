from repro.configs.registry import ARCHS, ESTIMATED, get_config

__all__ = ["ARCHS", "ESTIMATED", "get_config"]
