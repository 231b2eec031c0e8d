"""Reader ``setup_seconds``: process start to the window's first job —
interpreter and jax start, corpus, oracle, cache reload, warm-up jobs."""


def read(spec, env):
    return env.setup_s
