"""Architecture configs: one module per assigned arch (+ smoke variants)."""

from .base import ARCH_IDS, ModelConfig, load_arch, load_smoke, registry  # noqa: F401
