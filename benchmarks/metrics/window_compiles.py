"""Layer: compile. XLA compile requests inside the window, from JAX's
monitoring events (every compile of the process). Should read 0."""


def read(r):
    return r.window.window_compiles["compiles"]
