"""The step programs of every model kind, lowered and not run: a decode tick,
a mixed round and a K = 2 block of a tiny engine's geometry, held to the
hashes of ``tests/data/step_programs_pr59.json``.  PR 59 changed the mixed
round of the six kinds whose layers walk K/V pages through ``_kv_walk`` (the
chunk rows a lane at a time) and nothing else: every tick, every K = 2 block
and the mixed rounds of the four other kinds are held to PR 56's file
(``step_programs_pr56.json``) letter for letter, whose eight oldest kinds
repeat PR 54's.  A PR that adds a kind shows with it that the kinds before it
lower to the text they had; a PR that changes a program on purpose writes
the file again (``python tests/test_step_programs.py`` prints it).
"""

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import test_engine_plan
from tpulab.engine.paged import ContinuousBatcher
from tpulab.models.spec import init_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(ROOT, "tests", "data", "step_programs_pr59.json")
GOLDEN_PR56 = os.path.join(ROOT, "tests", "data", "step_programs_pr56.json")
GOLDEN_PR54 = os.path.join(ROOT, "tests", "data", "step_programs_pr54.json")
#: the kinds whose layers walk K/V pages through ``paged_steps._kv_walk``:
#: PR 59 changed their mixed round (the others' attention is latent or
#: sparse: another branch of ``_layer_block``)
KV_WALK_KINDS = ("dense", "jamba-mamba", "qwen3next-gdn", "evabyte-eva",
                 "zaya", "mellum")


def _lowered(spec, vocab, d_ff, kw):
    """``(texts without locations, texts with scopes)`` of a decode tick, a
    mixed round and a K = 2 block of a tiny engine's geometry, lowered and
    not run."""
    from tpulab.models.transformer import init_transformer_params
    if spec is None:
        params = init_transformer_params(vocab=vocab, d_model=32, n_heads=2,
                                         n_layers=2, d_ff=d_ff)
        heads, layers = 2, 2
    else:
        params = init_params(spec, vocab, d_ff)
        heads, layers = spec.n_heads, spec.n_layers
    cb = ContinuousBatcher(params, heads, layers, spec=spec,
                           compute_dtype=jnp.float32,
                           **dict(kw, use_kernel=False))
    try:
        lanes, fields = cb.lanes, cb.programs.fields
        size = lambda kind: sum(
            int(np.prod([n for n in shape if n >= 0])) for _n, _d, shape
            in fields[kind])
        tick = jnp.zeros((size("tick"),), jnp.int32)
        rnd = jnp.zeros((size("round") - 3 + 3 * (8 + lanes),), jnp.int32)
        blk = jnp.zeros((size("block"),), jnp.int32)
        lowered = [
            cb.programs.tick.lower(cb.params, cb._kv_state, tick),
            cb.programs.mixed.lower(cb.params, cb._kv_state, rnd,
                                    cb._no_carry),
            cb.programs.block(2).lower(cb.params, cb._kv_state, blk,
                                       cb._no_carry)]
        return ([low.as_text() for low in lowered],
                "\n".join(low.as_text(debug_info=True) for low in lowered))
    finally:
        cb.shutdown()


def _kinds():
    import test_longcat_flash as tl
    import test_mellum as tm
    import test_xing4 as tx
    import test_zaya as tz
    from tpulab.models.spec import (longcat_flash_spec, mellum_spec,
                                    xing4_spec, zaya_spec)
    small = dict(lanes=2, max_len=64, page_size=8)
    return dict(test_engine_plan.KINDS,
                longcat=(longcat_flash_spec(tl.CONFIG), tl.VOCAB, tl.D_FF,
                         small),
                xing4=(xing4_spec(tx.CONFIG), tx.VOCAB, tx.D_FF, small),
                zaya=(zaya_spec(tz.CONFIG), tz.VOCAB, 0, small),
                mellum=(mellum_spec(tm.CONFIG), tm.VOCAB, 0, small))


def _golden(path=GOLDEN):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("kind", ["dense", "glm-latent-moe", "jamba-mamba",
                                  "keye-indexer", "qwen3next-gdn",
                                  "evabyte-eva", "longcat", "xing4", "zaya"])
def test_the_other_kinds_programs_are_the_parents_text(kind):
    """Each kind lowers to the hashes of this PR's file (a hash of the
    StableHLO of a decode tick, a mixed round and a K = 2 block, without
    locations).  Against PR 56's file: the tick and the K = 2 block of
    EVERY kind are its hashes letter for letter, and so is the mixed round
    of the kinds whose attention is latent or sparse; the mixed round of a
    kind that walks K/V pages differs (PR 59: ``_kv_walk``) and nothing
    else does.  PR 56's file repeats PR 54's for the eight kinds that file
    held.  Only ``zaya`` holds a ``cca`` or ``res_scale`` scope, and none
    of the nine a table of a window group.

    A later PR that changes a program on purpose writes the file again:
    ``python tests/test_step_programs.py`` prints it."""
    golden, pr56, pr54 = (_golden(), _golden(GOLDEN_PR56),
                          _golden(GOLDEN_PR54))
    texts, scoped = _lowered(*_kinds()[kind])
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == golden["programs"][kind], (kind, got)
    tick, mixed, block = got
    before = pr56["programs"][kind]
    assert (tick, block) == (before[0], before[2])
    assert (mixed != before[1]) == (kind in KV_WALK_KINDS)
    assert (kind in golden["changed_in_pr59"]) == (kind in KV_WALK_KINDS)
    if kind != "zaya":
        assert before == pr54["programs"][kind]
    for scope in ("cca_", "res_scale"):
        assert (scope in scoped) == (kind == "zaya")
    assert "paged_mixed_step" in scoped


def test_the_new_kinds_programs_carry_two_groups_and_two_tables():
    """Kind ``mellum``: the hashes PR 59's file holds, the page store a
    PAIR of arrays of one and three layers that every program takes and
    returns, and a second table in every program's buffer."""
    golden, before = _golden(), _golden(GOLDEN_PR56)["programs"]["mellum"]
    spec, vocab, d_ff, kw = _kinds()["mellum"]
    texts, scoped = _lowered(spec, vocab, d_ff, kw)
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == golden["programs"]["mellum"], got
    assert set(golden["programs"]) == set(_kinds())
    # PR 56's tick and K = 2 block; its mixed round walks a lane at a time
    assert (got[0], got[2]) == (before[0], before[2]) and got[1] != before[1]
    assert set(golden["changed_in_pr59"]) == set(KV_WALK_KINDS)
    # 2 lanes x 8 pages of 8 rows: full (1, 17, 2, 8, 32), window (3, P, ...)
    for text in texts:
        assert "tensor<1x17x2x8x32xf32>" in text
        assert "tensor<3x" in text and "x2x8x32xf32>" in text
    from tpulab.engine.paged_steps import dispatch_fields
    for program in ("tick", "block", "round"):
        one = dispatch_fields(program, 2, 8)
        two = dispatch_fields(program, 2, 8, True)
        assert [f for f in two if f[0] != "wtables"] == list(one)


if __name__ == "__main__":
    # the golden, written again from this tree
    from tpulab.tpu.platform import force_cpu
    force_cpu(8)
    with open(GOLDEN, encoding="utf-8") as f:
        golden = json.load(f)
    golden["programs"] = {
        kind: [hashlib.sha256(t.encode()).hexdigest()[:16]
               for t in _lowered(*entry)[0]]
        for kind, entry in _kinds().items()}
    print(json.dumps(golden, indent=1))
