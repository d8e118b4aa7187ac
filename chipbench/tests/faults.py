"""Faults planted under the timed path, for ``test_faults.py``.

Each breaks the program where its work is produced; the harness itself
is untouched.

  * ``step_unchanged``: the Adam step returns its state unchanged;
  * ``params_unchanged``: the Adam step advances its moments but
    returns the parameters it was given;
  * ``update_flipped``: the Adam step moves the parameters against its
    update (the sign of the step flipped);
  * ``half_batch``: the Adam step drops half of its minibatch and takes
    the mean over the rest;
  * ``score_altered``: the scorer's presence probability of the first
    frame of every dispatch is moved by 1e-3;
  * ``answer_altered``: the oracle service reports every 50th frame it
    verifies with its presence flipped.

The cells are on one chip, so there is no exchange between chips to
leave out.
"""
from __future__ import annotations


def plant(name: str) -> None:
    if not name:
        return
    globals()["_" + name]()


def _step_unchanged():
    from repro.core import operators

    def factory():
        def step(params, m, v, *_rest, **_kw):
            return params, m, v
        return step

    operators._adam_step = factory


def _params_unchanged():
    from repro.core import operators

    orig = operators._adam_step

    def factory():
        step = orig()

        def frozen(params, *rest, **kw):
            _, m, v = step(params, *rest, **kw)
            return params, m, v
        return frozen

    operators._adam_step = factory


def _update_flipped():
    import jax

    from repro.core import operators

    orig = operators._adam_step

    def factory():
        step = orig()

        def flipped(params, *rest, **kw):
            new, m, v = step(params, *rest, **kw)
            return jax.tree_util.tree_map(lambda p, q: 2 * p - q, params,
                                          new), m, v
        return flipped

    operators._adam_step = factory


def _half_batch():
    from repro.core import operators

    orig = operators._adam_step

    def factory():
        step = orig()

        def half(params, m, v, xb, bright, ypb, ycb, *rest):
            h = max(xb.shape[0] // 2, 1)
            return step(params, m, v, xb[:h], bright[:h], ypb[:h], ycb[:h],
                        *rest)
        return half

    operators._adam_step = factory


def _score_altered():
    from repro.core.runtime import OperatorRuntime

    orig = OperatorRuntime._scorer_body

    def body(self, sig):
        scorer = orig(self, sig)

        def altered(params, x):
            p, c = scorer(params, x)
            return p.at[0].add(1e-3), c
        return altered

    OperatorRuntime._scorer_body = body


def _answer_altered():
    from repro.serving.oracle_service import OracleService

    orig = OracleService._verify_slot
    state = {"n": 0}

    def verify(self, batch):
        orig(self, batch)
        for t in batch:
            state["n"] += 1
            if state["n"] % 50 == 0:
                t.pos = not t.pos

    OracleService._verify_slot = verify
