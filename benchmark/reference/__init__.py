"""Plain references, one per family of configurations: forward pass and
loss in straightforward ``jax.numpy`` and float32, no kernel, no ``hvd``,
nothing imported from ``horovod_tpu``.  Each reads the system's parameter
tree by name.  The caller sets ``jax.default_matmul_precision("highest")``
around them (``check.py``): on a TPU a float32 matmul otherwise runs in
bfloat16 passes."""
