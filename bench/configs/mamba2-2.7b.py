"""Plain reference and weights of this configuration: a stack of Mamba-2
layers with the head tied to the embedding, in ``bench/model.py``."""
from bench.model import make_weights, served_readings  # noqa: F401
