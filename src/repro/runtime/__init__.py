"""repro.runtime — watchdog, metrics and the compile cache."""

from .compile_cache import enable_compile_cache
from .metrics import MetricsLogger
from .watchdog import StepHang, Watchdog

__all__ = ["MetricsLogger", "StepHang", "Watchdog", "enable_compile_cache"]
