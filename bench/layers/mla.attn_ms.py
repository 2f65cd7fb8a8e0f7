"""mla.attn_ms: device milliseconds per traced LM train step of the
latent attention (projections, RoPE, attention and the output projection,
forward, recomputation and backward), from the profiler trace.

The program marks it with ``jax.named_scope("mla")``
(``models/transformer.py``); the instructions under the scope are read from
the trainer's own step program compiled again from abstract shapes
(``bench/lm.py`` ``scope_ms``, as ``gcd.update_ms`` reads its scope).
Nothing is read when the program has no such scope.
"""


def read(run, reduced):
    from bench import lm

    return lm.scope_ms(run, reduced, "mla")
