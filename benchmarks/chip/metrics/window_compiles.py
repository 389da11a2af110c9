"""window_compiles: XLA compilations inside the measured window that the
persistent cache did not serve (JAX's compile events less its cache
hits). Set-up warms every program, so it should read 0."""


def read(r: dict):
    return r["window_compiles"]
