"""`peak_hbm_gb` of the Flower API cell, which moves `client_updates_per_s.flower`
there (the one-chip rate of the host-bound cell has its own bound)."""
from bench.metrics.peak_hbm_gb import read  # noqa: F401
