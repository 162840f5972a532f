"""The benchmark's own arithmetic: peaks, FLOPs, bytes, traffic, traces."""
