"""Faults planted under the timed path, for the tests and for reading a
broken run's numbers on the chip (tools/calibrate.py).  Each takes a
cluster after warm-up and breaks every engine's backend in place.
"""
from __future__ import annotations


def _each(cluster, fn) -> None:
    for eng in cluster.engines.values():
        fn(eng.backend)


def _alter_decoded_token(be):
    """Every third decode step, each active request's new token is replaced
    by the next id as it is produced (the model goes on from the true one)."""
    decode, vocab = be.decode, be.cfg.vocab_size
    steps = [0]

    def tampered(active, now):
        out = decode(active, now)
        steps[0] += 1
        if steps[0] % 3 == 0:
            for _slot, r in active:
                r.output_tokens[-1] = (r.output_tokens[-1] + 1) % vocab
        return out
    be.decode = tampered


def _alter_first_token(be):
    """The prefill's token is replaced by the next id."""
    start, vocab = be.start, be.cfg.vocab_size

    def tampered(r, now):
        out = start(r, now)
        r.output_tokens[0] = (r.output_tokens[0] + 1) % vocab
        return out
    be.start = tampered


def _keep_kv_unchanged(be):
    """The decode step hands back the KV pages it was given: each step's
    state is left unchanged."""
    step = be._jit_decode_paged

    def tampered(params, tokens, pages, *rest):
        logits, _new, aux = step(params, tokens, pages, *rest)
        return logits, pages, aux
    be._jit_decode_paged = tampered


FAULTS = {"decoded_token_altered": _alter_decoded_token,
          "first_token_altered": _alter_first_token,
          "decode_leaves_kv_unchanged": _keep_kv_unchanged}


def plant(name: str):
    """A ``tamper`` for run.measure that plants fault ``name``."""
    return lambda cluster: _each(cluster, FAULTS[name])
