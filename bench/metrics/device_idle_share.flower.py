"""`device_idle_share` of the Flower API cell, which moves `client_updates_per_s.flower`
there (the one-chip rate of the host-bound cell has its own bound)."""
from bench.metrics.device_idle_share import read  # noqa: F401
