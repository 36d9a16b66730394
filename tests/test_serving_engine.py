"""Serving engine: greedy generation matches step-by-step teacher forcing
and honors EOS stopping."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import forward, init_params
from repro.serving.lm import Engine, ServeConfig


def test_greedy_matches_forward_argmax():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b, s, n_new = 2, 16, 4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size)
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=n_new))
    gen = eng.generate({"tokens": tokens})
    assert gen.shape == (b, n_new)

    # oracle: iterative full forward + argmax (teacher-forced replay)
    cur = np.asarray(tokens)
    for t in range(n_new):
        logits = forward(params, {"tokens": jnp.asarray(cur)}, cfg)
        nxt = np.asarray(
            jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1))
        assert np.array_equal(gen[:, t], nxt), t
        cur = np.concatenate([cur, nxt[:, None]], axis=1)


def test_eos_stops_and_masks():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    eng = Engine(cfg, params, ServeConfig(max_new_tokens=6))
    gen = eng.generate({"tokens": tokens})
    # pick the first generated token as a fake EOS: everything after the
    # first occurrence must be masked to EOS
    eos = int(gen[0, 0])
    eng2 = Engine(cfg, params, ServeConfig(max_new_tokens=6, eos_id=eos))
    gen2 = eng2.generate({"tokens": tokens})
    for row in gen2:
        hits = np.where(row == eos)[0]
        if len(hits):
            assert (row[hits[0]:] == eos).all()
