"""Pallas kernels: the ranking score / victim order (``ranking_score``) and the
per-lane state scatter (``lane_scatter``)."""
