# TPU Pallas kernels for liquidSVM's compute hot spots (the parts the paper
# implements with SIMD/CUDA):
#   kernel_matrix    — tiled Gram-matrix computation (MXU cross term)
#   svm_predict      — fused K(test, SV) @ coefs evaluation, no Gram in HBM
#   flash_attention  — causal/windowed/bidirectional flash for the LM stack
# Each package ships <name>.py (pallas_call + BlockSpec), ops.py (jit'd
# dispatching wrapper), ref.py (pure-jnp oracle used by tests).
