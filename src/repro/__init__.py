"""repro: TokenWeave — efficient compute-communication overlap for distributed
LLM inference — reproduced and extended as a TPU-native JAX framework."""

__version__ = "0.1.0"
