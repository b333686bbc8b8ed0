"""Set-up: programs built inside the measured window.

Counter: JAX's ``/jax/core/compile/backend_compile_duration`` events
(a compile or a persistent-cache read) between the window's start and
end.  Set-up warms every shape, so this reads 0."""


def read(run):
    return run["compiles_in_window"]
