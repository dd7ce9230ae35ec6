"""Pallas TPU kernels for the hot ops.

Hand-fused kernels where XLA's automatic fusion leaves HBM bandwidth on
the table.  Each kernel has the same contract as its XLA counterpart in
ops/ and is opt-in via config (``use_pallas``) with automatic fallback
off-TPU (interpret mode keeps them testable on the CPU pseudo-cluster).
"""
