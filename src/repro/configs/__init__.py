from .base import ArchConfig
from .registry import ARCHS, ARCH_NAMES, get_arch

__all__ = ["ArchConfig", "ARCHS", "ARCH_NAMES", "get_arch"]
