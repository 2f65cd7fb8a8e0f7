"""latent_gcd.update_ms: device milliseconds per traced LM train step of
the GCD update of the stacked latent rotations (scoring, pair selection and
the apply, vmapped over the layers), from the profiler trace: the ops under
``jax.named_scope("gcd")`` (``rotations/gcd.py``) in this cell's step
program, read as ``gcd.update_ms`` reads them (``bench/lm.py``
``scope_ms``)."""


def read(run, reduced):
    from bench import lm

    return lm.scope_ms(run, reduced, "gcd")
