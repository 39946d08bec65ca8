"""`round_mfu` of the Flower API cell, which moves `client_updates_per_s.flower`
there (the one-chip rate of the host-bound cell has its own bound)."""
from bench.metrics.round_mfu import read  # noqa: F401
