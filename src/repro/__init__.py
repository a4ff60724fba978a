"""repro: communication-characterized multi-pod JAX training/serving framework.

Reproduction of "Exploring GPU-to-GPU Communication: Insights into Supercomputer
Interconnects" (SC'24), adapted to a TPU v5e multi-pod target.  See DESIGN.md.
"""
__version__ = "1.0.0"
