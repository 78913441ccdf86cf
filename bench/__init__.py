"""The chip benchmark of the thinned feature engine (see PERF.md)."""
