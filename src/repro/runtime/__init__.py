from .abft_guard import ABFTGuard, GuardConfig, GuardRefused  # noqa: F401
from .watchdog import StragglerWatchdog  # noqa: F401
