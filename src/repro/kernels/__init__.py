"""Pallas TPU kernels for the perf-critical compute layers.

  sdca             -- Procedure P (LocalSDCA) as one kernel: H sequential
                      closed-form coordinate steps, with leaves packed into
                      the sublanes of every vreg so each step serves many
                      leaves, and their drawn rows streamed through VMEM
                      (the paper's compute hot spot, TPU-adapted).
  flash_attention  -- blocked online-softmax causal/GQA/windowed attention
                      (the LM stack's dominant non-matmul HBM term).
  rglru            -- the RG-LRU diagonal recurrence (Griffin) as a
                      chunked parallel-prefix kernel: one HBM read of
                      (a, b) + one write of h total (the associative_scan
                      oracle materializes O(log S) full intermediates).

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper) and ref.py (pure-jnp oracle); tests sweep shapes/dtypes in
interpret mode against the oracle, and tests/test_tpu_compile.py compiles
each for a described v5e chip.
"""
