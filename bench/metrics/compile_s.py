"""compile_s: seconds of XLA backend compilation in set-up, the sum of
JAX's `/jax/core/compile/backend_compile_duration` events; programs found
in the persistent compilation cache add nothing."""


def read(run):
    return run.compile_setup_s
