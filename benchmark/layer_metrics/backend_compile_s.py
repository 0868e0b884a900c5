"""Compile layer: what JAX spent in the backend compiler and in reading the
persistent cache during set-up, from the program's ``jax.monitoring``
listeners (``jax_backend_compile_seconds`` + ``jax_cache_retrieval_seconds``;
a retrieval inside a compile call is counted once). Read from the snapshot
taken where set-up ends."""


def read(m):
    hists = m["before"]["histograms"]
    found = [hists[k]["sum"] for k in ("jax_backend_compile_seconds",
                                       "jax_cache_retrieval_seconds")
             if k in hists]
    return sum(found) if found else None
