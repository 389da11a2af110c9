"""compile_s: seconds of XLA compilation in set-up, from JAX's own
``backend_compile_duration`` events (a load from the persistent cache
counts its load time)."""


def read(r: dict):
    return r["setup_compile_s"]
