"""trino_tpu — a TPU-native distributed SQL query engine.

A from-scratch reimplementation of the capabilities of Trino (reference:
jirassimok/trino, Trino 356-SNAPSHOT) designed TPU-first:

- Columnar batches are structs of fixed-width device arrays with validity
  masks (reference: ``core/trino-spi/src/main/java/io/trino/spi/Page.java``).
- The "codegen tier" (reference: ``core/trino-main/.../sql/gen/``) is XLA:
  expression IR is traced into jnp ops and jit-compiled.
- Group-by/joins use sort + segment-reduce formulations that map to the MXU
  and avoid scatter-heavy hash tables (reference hash specs:
  ``operator/MultiChannelGroupByHash.java``, ``operator/PagesHash.java``).
- Distribution is SPMD over a ``jax.sharding.Mesh``; Trino's HTTP shuffle
  (reference: ``execution/buffer/``, ``operator/ExchangeClient.java``)
  becomes ``lax.all_to_all``/``psum`` collectives over ICI.
"""

from trino_tpu.config import enable_x64

enable_x64()


def _enable_compile_cache() -> None:
    """Persistent XLA compile cache: plans are re-traced per query (like the
    reference re-plans per query), but identical fragment programs hit the
    on-disk XLA cache instead of recompiling.

    The one rule: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and nothing here touches the directory; otherwise it is
    ``<checkout>/.jax_cache``, a fixed path (the path is part of the cache
    key). Worker subprocesses import this package and so agree."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(checkout, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


_enable_compile_cache()

__version__ = "0.1.0"
