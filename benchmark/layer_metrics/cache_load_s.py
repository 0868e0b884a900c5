"""Compile layer: what JAX spent reading compiled programs back from the
persistent cache during set-up (``jax_cache_retrieval_seconds``, from the
program's ``jax.monitoring`` listener): the warm run's stand-in for the
backend compile. ``backend_compile_s`` holds it too, with the compiles.
Read from the snapshot taken where set-up ends. A program that listened
(any ``jax_*_seconds`` series is there) and retrieved nothing reads 0;
one with no listener gives nothing to read."""


def read(m):
    hists = m["before"]["histograms"]
    if not any(k.startswith("jax_") and k.endswith("_seconds")
               for k in hists):
        return None
    return hists.get("jax_cache_retrieval_seconds", {"sum": 0.0})["sum"]
