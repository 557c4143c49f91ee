"""Compile requests inside the window that JAX's persistent compilation
cache did not serve (`/jax/compilation_cache/compile_requests_use_cache` less
`/jax/compilation_cache/cache_hits`, counted by the driver).  The window
compiles nothing: anything but 0 also fails the run's `correct`."""


def read(run: dict):
    n = run.get("compiles_in_window")
    return None if n is None else float(n)
