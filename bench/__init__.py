"""On-chip benchmark of the da4ml serving and emulation paths (see PERF.md)."""
