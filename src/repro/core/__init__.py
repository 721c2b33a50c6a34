"""The CP core: COO tensors, partitioning, the distributed MTTKRP and the
ALS sweep (paper Algorithms 1–3)."""
