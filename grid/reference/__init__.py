"""Plain float32 ``jax.numpy`` forwards of each configuration, written from
the published descriptions and independent of ``paddle_tpu/models``: what
``correct`` compares the system with."""
