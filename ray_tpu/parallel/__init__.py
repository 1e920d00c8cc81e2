"""Cross-device pieces that are not the learner's mesh runtime
(``ray_tpu.sharding``): collectives, ring attention and the
jax.distributed bring-up."""
