"""Reproduction of "Gradient Sparsification for Communication-Efficient
Distributed Optimization" (Wangni et al., NIPS 2018) grown toward a
production-scale jax/pallas training system."""
