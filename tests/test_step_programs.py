"""The step programs of every model kind, lowered and not run: a decode tick,
a mixed round and a K = 2 block of a tiny engine's geometry, held to the
hashes of ``tests/data/step_programs_pr54.json``.  A PR that adds a kind
shows with it that the kinds before it lower to the text they had; a PR that
changes a program on purpose writes the file again (``python
tests/test_step_programs.py`` prints it).
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
GOLDEN = os.path.join(ROOT, "tests", "data", "step_programs_pr54.json")


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
    import test_xing4 as tx
    from tpulab.models.spec import longcat_flash_spec, xing4_spec
    small = dict(lanes=2, max_len=64, page_size=8)
    return dict(test_engine_plan.KINDS,
                longcat=(longcat_flash_spec(tl.CONFIG), tl.VOCAB, tl.D_FF,
                         small),
                xing4=(xing4_spec(tx.CONFIG), tx.VOCAB, tx.D_FF, small))


@pytest.mark.parametrize("kind", ["dense", "glm-latent-moe", "jamba-mamba",
                                  "keye-indexer", "qwen3next-gdn",
                                  "evabyte-eva", "longcat", "xing4"])
def test_the_other_kinds_programs_are_the_parents_text(kind):
    """The eight kinds the benchmark already had lower to the text they had
    at the parent of PR 54 (``tests/data/step_programs_pr54.json``: a hash
    of the StableHLO of a decode tick, a mixed round and a K = 2 block,
    without locations), and hold no ``cca`` or ``res_scale`` scope.  The two
    kinds with a lane state are the exception the file states: their
    convolution now reads the window ``_segment_window`` gathers, the same
    arithmetic in another order (a tick and a block) and as one weighted sum
    over the stacked window (a round); their hashes are this PR's.

    A later PR that changes a program on purpose writes the file again:
    ``python tests/test_step_programs.py`` prints it."""
    with open(GOLDEN, encoding="utf-8") as f:
        golden = json.load(f)
    texts, scoped = _lowered(*_kinds()[kind])
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == golden["programs"][kind], (kind, got)
    assert (kind in golden["changed_in_pr54"]) == (kind in ("jamba-mamba",
                                                            "qwen3next-gdn"))
    for scope in ("cca_", "res_scale"):
        assert scope not in scoped
    assert "paged_mixed_step" in scoped


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
