"""Plain references, one module per configuration, found by the
configuration file's ``reference`` key. float32, matmul precision
"highest", no kernels, nothing imported from ``ray_tpu``."""
