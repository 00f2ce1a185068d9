"""Reproduction of "Optimal Complexity in Non-Convex Decentralized Learning
over Time-Varying Networks" as a production-scale jax system.

Importing :mod:`repro` has no side effects: subpackages are imported on
demand, and nothing here touches a device or the jax configuration.
"""
