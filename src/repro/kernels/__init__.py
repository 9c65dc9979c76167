"""Pallas TPU kernels for the platform's compute hot spots (paper §2.3).

The paper offloads hot kernels to accelerators via OpenCL (conv 10-20x,
ICP 30x).  Here each hot spot is a `pl.pallas_call` kernel with explicit
BlockSpec VMEM tiling, a jitted wrapper (ops.py) and a pure-jnp oracle
(ref.py).  Kernels run `interpret=True` on CPU (validation) and compiled on
TPU (the target).

  flash_attention/  -- causal/full attention with a backward: JAX's splash
                       kernels vmapped over KV heads (LM training hot spot)
  decode_attention/ -- paged GQA decode attention over block tables (serving)
  ssd/              -- Mamba-2 SSD chunk scan (SSM archs)
  icp/              -- ICP nearest-neighbor correspondence (HD map generation)
  conv2d/           -- im2col-MXU convolution (perception CNN / simulation)
"""
