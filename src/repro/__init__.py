"""repro — parallel selected inversion (PSelInv) with tree-based
restricted collectives, as a JAX engine and server that run on TPU."""
__version__ = "0.1.0"
