"""FlooNoC-JAX: a multi-pod JAX training/serving framework built on
FlooNoC's narrow-wide, endpoint-ordered, dimension-routed NoC principles."""
__version__ = "0.1.0"
