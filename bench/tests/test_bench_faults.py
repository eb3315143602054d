"""A run with the timed path broken underneath comes out not correct:
once for each fault a one-chip serving cell can have.  (There is no
exchange between chips to leave out on one chip.)"""
import jax.numpy as jnp
import pytest

import tiny

# enough load that every slot of the tiny pool is busy
BUSY = dict(tiny.TRAFFIC, rate_per_s=60.0)


def _wrap_decode(fault):
    """A program hook: every decode step's output passes through
    ``fault(logits, new_cache, old_cache) -> (logits, cache)``."""
    def hook(engine):
        build = engine._decode_exec

        def patched(cols=0, s=1):
            step = build(cols, s)

            def run(params, cache, tok, pos):
                logits, new = step(params, cache, tok, pos)
                return fault(logits, new, cache)
            return run
        engine._decode_exec = patched
    return hook


def state_unchanged(logits, new, old):
    return logits, old                      # the KV written is dropped


def half_batch(logits, new, old):
    h = logits.shape[0] // 2                # second half: first half's rows
    return logits.at[h:2 * h].set(logits[:h]), new


def token_altered(logits, new, old):
    return jnp.roll(logits, 1, axis=-1), new    # each token: its neighbour


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(fault):
    r = tiny.run(trace=False, seed=21, traffic=BUSY,
                 program_hook=_wrap_decode(fault))
    assert not r["correct"], r["checks"]
