"""The benchmark's yardstick: finding a cell and its files by name, the
backlog generator, the window's instruments, the trace reduction, the
FLOPs functions, the table of peaks and the comparison that decides
``correct``.  Everything that belongs to one configuration, one traffic
mix or one metric lives in its own file under ``configs/``,
``workloads/`` or ``metrics/``."""
