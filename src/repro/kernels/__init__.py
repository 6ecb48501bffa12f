"""Pallas TPU kernels: flash_attention, moe_dispatch (the held-expert
layer's pair gather and combine), ssd (mamba2), mlstm (xLSTM).

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper + custom_vjp), ref.py (pure-jnp oracle).
"""
