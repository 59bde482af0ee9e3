"""Faults planted in the timed path, to show that the comparison catches
them: each is called with the engine before the window (harness.run's
`fault`) and breaks its decode step underneath. Faults a served cell can
have on one chip: a step that returns its state unchanged (the new
tokens' KV never lands in the pool), and a token altered where it is
produced. (One chip: no exchange between chips to leave out; serving
has no batch mean to take over half a batch.)"""
from __future__ import annotations


def state_unchanged(eng) -> None:
    import jax

    from repro.serve import state_store
    fn = state_store.make_step_fns(eng.cfg, eng.store,
                                   rules=eng.rules)["decode"]
    eng._decode = jax.jit(lambda p, s, b: (fn(p, s, b)[0], s))


def token_altered(eng) -> None:
    import jax.numpy as jnp
    decode = eng._decode

    def shifted(p, s, b):
        logits, s = decode(p, s, b)
        return jnp.roll(logits, 1, axis=-1), s    # argmax moves one id up
    eng._decode = shifted


FAULTS = {"state-unchanged": state_unchanged, "token-altered": token_altered}
