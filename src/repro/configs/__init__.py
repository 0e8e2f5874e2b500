"""Architecture registry: one module per assigned architecture."""
from repro.configs.registry import ARCH_IDS, get_config  # noqa: F401
