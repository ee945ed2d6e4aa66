"""repro: JAX/TPU expert-parallel training & inference framework reproducing
"NCCL EP: Towards a Unified Expert Parallel Communication API for NCCL"."""
__version__ = "0.1.0"
