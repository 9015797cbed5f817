"""Word-level reasoning tier (smt/word_tier.py + ops/word_prop.py).

Covers the acceptance surface of the tier: word-level UNSAT/SAT
decisions pinned against the native solver oracle on random term DAGs,
scalar-vs-batched executor parity, hint (known-bits) soundness,
fixpoint convergence, the kill switch restoring the exact pre-tier
funnel, and checkpoint/resume invalidation of tier state.
"""

import os
import random

import pytest

from mythril_tpu.native import SatSolver
from mythril_tpu.smt import terms as T
from mythril_tpu.smt.solver import get_blast_context, reset_blast_context
from mythril_tpu.smt.word_tier import (
    get_word_tier,
    hint_literals,
    reset_word_tier,
    tightening_digest,
    word_tier_enabled,
)

pytestmark = pytest.mark.word


@pytest.fixture(autouse=True)
def _fresh_context():
    reset_blast_context()
    reset_word_tier()
    from mythril_tpu.ops.batched_sat import dispatch_stats

    dispatch_stats.reset()
    yield
    reset_blast_context()
    reset_word_tier()


def _decide_one(nodes):
    ctx = get_blast_context()
    verdicts, hints, envs = get_word_tier().decide(ctx, [nodes])
    return verdicts[0], hints[0], envs[0]


# ---------------------------------------------------------------------------
# decision rules
# ---------------------------------------------------------------------------


def test_interval_unsat_decided():
    x = T.var("iv", 256)
    v, _, _ = _decide_one(
        [T.ult(x, T.const(5, 256)), T.ult(T.const(7, 256), x)]
    )
    assert v is False


def test_known_bits_contradiction_decided():
    x = T.var("kb", 256)
    c1 = T.eq(T.bv_and(x, T.const(1, 256)), T.const(1, 256))
    c2 = T.eq(T.bv_and(x, T.const(3, 256)), T.const(0, 256))
    v, _, _ = _decide_one([c1, c2])
    assert v is False


def test_dead_branch_shape_from_tree_prefix():
    """The scale-contract dead-leaf shape: low-bit equalities that
    contradict already-asserted selector bits die without CNF."""
    x = T.var("tree", 256)
    bit0 = T.eq(T.bv_and(x, T.const(1, 256)), T.const(1, 256))
    bit1 = T.eq(T.bv_and(x, T.const(2, 256)), T.const(2, 256))
    guard = T.eq(T.bv_and(x, T.const(3, 256)), T.const(1, 256))
    v, _, _ = _decide_one([bit0, bit1, guard])
    assert v is False
    from mythril_tpu.ops.batched_sat import dispatch_stats

    assert dispatch_stats.word_decided_unsat >= 1
    assert dispatch_stats.word_prop_s > 0.0


def test_valid_constraint_decides_sat():
    x = T.var("vs", 256)
    # (x & 0xF) <= 0xF is valid but does NOT constant-fold at
    # construction time — the tier's forward pass proves it
    c = T.ule(T.bv_and(x, T.const(0xF, 256)), T.const(0xF, 256))
    v, _, env = _decide_one([c])
    assert v is True
    assert env is not None


def test_sat_by_pinned_model():
    x = T.var("pm", 256)
    v, _, env = _decide_one(
        [T.eq(x, T.const(5, 256)), T.ult(x, T.const(10, 256))]
    )
    assert v is True
    assert env.variables[x.id] == 5
    from mythril_tpu.ops.batched_sat import dispatch_stats

    assert dispatch_stats.word_decided_sat == 1


def test_selector_alone_decides_sat_by_pinned_model():
    """A lone function-selector equation pins the word's top bits, and
    the pinned assignment already IS a model — decided SAT pre-CNF."""
    y = T.var("selsat", 256)
    c = T.eq(T.lshr(y, T.const(224, 256)), T.const(0xDEADBEEF, 256))
    v, _, env = _decide_one([c])
    assert v is True
    assert env.variables[y.id] >> 224 == 0xDEADBEEF


def test_selector_shape_hints():
    """Function-selector equations pin the calldata word's top bits —
    with a residue the tier cannot close, the tightening survives as
    the known-bits hint the blaster turns into unit assumptions."""
    y = T.var("sel", 256)
    c = T.eq(T.lshr(y, T.const(224, 256)), T.const(0xDEADBEEF, 256))
    # probe-resistant residue over the SAME word keeps the lane open
    residue = T.eq(
        T.bv_and(T.mul(y, T.const(0x6D2B, 256)), T.const(0xFFFF, 256)),
        T.const(0x1234, 256),
    )
    v, hints, _ = _decide_one([c, residue])
    assert v is None  # the multiplier guard stays for the blaster
    mask, val = hints[y.id]
    assert mask & (0xFFFFFFFF << 224) == 0xFFFFFFFF << 224
    assert val >> 224 == 0xDEADBEEF
    from mythril_tpu.ops.batched_sat import dispatch_stats

    assert dispatch_stats.word_tightened_bits >= 32


def test_cross_constraint_sharing_via_interning():
    """Two constraints over the same interned subterm refine ONE slot:
    the contradiction needs no bit-level reasoning."""
    x = T.var("shared", 256)
    masked = T.bv_and(x, T.const(0xFF, 256))
    v, _, _ = _decide_one(
        [T.eq(masked, T.const(5, 256)), T.eq(masked, T.const(7, 256))]
    )
    assert v is False


def test_unsupported_ops_stay_open_and_sound():
    arr = T.avar("store", 256, 256)
    x = T.var("uo", 256)
    c = T.eq(T.select(arr, x), T.const(1, 256))
    v, hints, _ = _decide_one([c])
    assert v is None  # select is opaque: no decision, no wrong hints
    assert not hints


def test_decisions_populate_unsat_memo():
    ctx = get_blast_context()
    x = T.var("memo", 256)
    nodes = [T.ult(x, T.const(2, 256)), T.ult(T.const(9, 256), x)]
    v, _, _ = _decide_one(nodes)
    assert v is False
    key = tuple(sorted(n.id for n in nodes))
    assert ctx.unsat_memo_hit(key)  # the CDCL tail inherits the verdict


# ---------------------------------------------------------------------------
# fixpoint behavior
# ---------------------------------------------------------------------------


def test_fixpoint_convergence_across_rounds(monkeypatch):
    """A chain that needs backward+forward interleaving converges, and
    extra rounds change nothing (the transfer functions are monotone:
    once a fixpoint is reached, more rounds are identity)."""
    x = T.var("fx", 256)
    y = T.var("fy", 256)
    masked = T.bv_and(x, T.const(0xFF, 256))
    chain = [
        T.eq(y, masked),
        T.eq(y, T.const(7, 256)),
        T.eq(masked, T.const(9, 256)),
    ]
    results = {}
    for rounds in (2, 4, 8):
        reset_word_tier()
        monkeypatch.setenv("MYTHRIL_TPU_WORD_ROUNDS", str(rounds))
        results[rounds] = _decide_one(chain)
    assert results[2][0] is False
    assert results[2] == results[4] == results[8]


def test_backward_inverts_arithmetic_chain():
    """known-bits flow backward through add-const / xor-const onto the
    variable (the _push_bv_down inverse transfer)."""
    x = T.var("inv", 256)
    c = T.eq(
        T.bv_xor(T.add(x, T.const(17, 256)), T.const(0xAA, 256)),
        T.const(0x1234, 256),
    )
    v, hints, _ = _decide_one([c])
    # add-const/xor-const are bijections: x is fully pinned, and the
    # pinned assignment IS a model, so the tier decides SAT
    expected = ((0x1234 ^ 0xAA) - 17) & ((1 << 256) - 1)
    if v is True:
        pass  # decided by the pinned model — strongest outcome
    else:
        mask, val = hints[x.id]
        assert mask == (1 << 256) - 1
        assert val == expected


# ---------------------------------------------------------------------------
# oracle + parity on random DAGs
# ---------------------------------------------------------------------------

_WIDTH = 8


def _rand_term(rng, depth, vars_):
    if depth == 0 or rng.random() < 0.3:
        if rng.random() < 0.5:
            return rng.choice(vars_)
        return T.const(rng.getrandbits(_WIDTH), _WIDTH)
    op = rng.choice(
        ["add", "sub", "mul", "and", "or", "xor", "not", "shl",
         "lshr", "ite", "zx", "sx"]
    )
    a = _rand_term(rng, depth - 1, vars_)
    b = _rand_term(rng, depth - 1, vars_)
    if op == "not":
        return T.bv_not(a)
    if op == "shl":
        return T.shl(a, T.const(rng.randrange(0, _WIDTH + 3), _WIDTH))
    if op == "lshr":
        return T.lshr(a, T.const(rng.randrange(0, _WIDTH + 3), _WIDTH))
    if op == "ite":
        return T.ite(_rand_pred(rng, 1, vars_), a, b)
    if op == "zx":
        return T.extract(_WIDTH - 1, 0,
                         T.add(T.zext(8, a), T.zext(8, b)))
    if op == "sx":
        return T.extract(_WIDTH - 1, 0, T.sext(4, a))
    return {"add": T.add, "sub": T.sub, "mul": T.mul, "and": T.bv_and,
            "or": T.bv_or, "xor": T.bv_xor}[op](a, b)


def _rand_pred(rng, depth, vars_):
    if depth == 0 or rng.random() < 0.4:
        a, b = _rand_term(rng, 2, vars_), _rand_term(rng, 2, vars_)
        return rng.choice([T.eq, T.ult, T.ule, T.slt, T.sle])(a, b)
    op = rng.choice(["band", "bor", "bnot"])
    if op == "bnot":
        return T.bnot(_rand_pred(rng, depth - 1, vars_))
    return {"band": T.band, "bor": T.bor}[op](
        _rand_pred(rng, depth - 1, vars_),
        _rand_pred(rng, depth - 1, vars_),
    )


def _oracle_check(ctx, nodes, monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    try:
        return ctx.check(nodes, timeout_s=10.0)[0]
    finally:
        monkeypatch.delenv("MYTHRIL_TPU_WORD_TIER")


def test_random_dags_vs_native_oracle(monkeypatch):
    """Every word-tier verdict on random term DAGs must agree with the
    native solver, and every hinted bit must be implied (asserting its
    negation alongside the constraints is UNSAT)."""
    rng = random.Random(1234)
    decided = 0
    hint_bits = 0
    for trial in range(120):
        reset_blast_context()
        reset_word_tier()
        ctx = get_blast_context()
        vars_ = [T.var(f"o{trial}_{i}", _WIDTH) for i in range(3)]
        nodes = [
            _rand_pred(rng, 2, vars_)
            for _ in range(rng.randrange(1, 5))
        ]
        nodes = [n for n in nodes if n not in (T.TRUE, T.FALSE)]
        if not nodes:
            continue
        verdicts, hints, _ = get_word_tier().decide(ctx, [nodes])
        verdict = verdicts[0]
        if verdict is not None:
            decided += 1
            status = _oracle_check(ctx, nodes, monkeypatch)
            expected = SatSolver.UNSAT if verdict is False else SatSolver.SAT
            assert status == expected, (trial, verdict, nodes)
            continue
        lane_hints = hints[0] or {}
        for nid, (mask, val) in lane_hints.items():
            var_node = next(v for v in vars_ if v.id == nid)
            bit = (mask & -mask).bit_length() - 1  # lowest hinted bit
            bitval = (val >> bit) & 1
            probe = T.eq(
                T.bv_and(T.lshr(var_node, T.const(bit, _WIDTH)),
                         T.const(1, _WIDTH)),
                T.const(1 - bitval, _WIDTH),
            )
            status = _oracle_check(ctx, nodes + [probe], monkeypatch)
            assert status == SatSolver.UNSAT, (trial, nid, bit, nodes)
            hint_bits += 1
    assert decided >= 10  # the tier must actually decide a share
    assert hint_bits >= 5


def test_scalar_and_batched_executors_agree(monkeypatch):
    """The per-lane scalar walk and the batched limb-plane kernels are
    two executors of one algorithm — verdicts and hints must match."""
    rng = random.Random(99)
    checked = 0
    for trial in range(25):
        reset_blast_context()
        ctx = get_blast_context()
        vars_ = [T.var(f"p{trial}_{i}", _WIDTH) for i in range(2)]
        lanes = [
            [_rand_pred(rng, 2, vars_)
             for _ in range(rng.randrange(1, 4))]
            for _ in range(4)
        ]
        reset_word_tier()
        monkeypatch.setenv("MYTHRIL_TPU_WORD_XP", "scalar")
        v1, h1, _ = get_word_tier().decide(ctx, lanes)
        reset_word_tier()
        monkeypatch.setenv("MYTHRIL_TPU_WORD_XP", "numpy")
        v2, h2, _ = get_word_tier().decide(ctx, lanes)
        monkeypatch.delenv("MYTHRIL_TPU_WORD_XP")
        assert v1 == v2, (trial, v1, v2)
        assert h1 == h2, (trial, h1, h2)
        checked += 1
    assert checked == 25


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_auto_executor_is_scalar_on_every_backend(monkeypatch, backend):
    """The eager jax executor is opt-in only: on a v5e it was the auto
    pick for 16+ lanes and ran one dispatch per op, so a wide-frontier
    analysis hit its execution timeout (PR 21)."""
    from mythril_tpu.ops import device_health

    monkeypatch.delenv("MYTHRIL_TPU_WORD_XP", raising=False)
    monkeypatch.setattr(device_health, "backend_name", lambda: backend)
    assert get_word_tier()._executor(64) == "scalar"


def test_jax_device_executor_agrees(monkeypatch):
    """One batch through the jax.numpy limb-plane executor (the device
    path, CPU backend here) must match the scalar host walk.  Kept
    small: eager jnp dispatches are slow off-device."""
    x = T.var("jx", 256)
    ctx = get_blast_context()
    lanes = [
        [T.ult(x, T.const(5, 256)), T.ult(T.const(7, 256), x)],
        [T.eq(T.lshr(x, T.const(224, 256)), T.const(0xFEED, 256)),
         T.eq(T.bv_and(T.mul(x, T.const(3, 256)), T.const(0xFF, 256)),
              T.const(0x42, 256))],
    ]
    monkeypatch.setenv("MYTHRIL_TPU_WORD_XP", "scalar")
    v1, h1, _ = get_word_tier().decide(ctx, lanes)
    reset_word_tier()
    monkeypatch.setenv("MYTHRIL_TPU_WORD_XP", "jax")
    v2, h2, _ = get_word_tier().decide(ctx, lanes)
    monkeypatch.delenv("MYTHRIL_TPU_WORD_XP")
    assert v1 == v2 == [False, None]
    assert h1 == h2


# ---------------------------------------------------------------------------
# kill switch / funnel parity
# ---------------------------------------------------------------------------


def test_kill_switch_disables_tier(monkeypatch):
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    assert not word_tier_enabled()
    x = T.var("ks", 256)
    v, hints, _ = _decide_one(
        [T.ult(x, T.const(5, 256)), T.ult(T.const(7, 256), x)]
    )
    assert v is None and hints is None
    from mythril_tpu.ops.batched_sat import dispatch_stats

    assert dispatch_stats.word_decided_unsat == 0
    assert dispatch_stats.word_prop_s == 0.0


def test_funnel_verdict_parity_with_kill_switch(monkeypatch):
    """BlastContext.check answers identically with the tier on and off
    over a mixed bag of random constraint sets (the end-to-end parity
    the bench pins corpus-wide)."""
    rng = random.Random(7)
    for trial in range(40):
        vars_ = [T.var(f"kp{trial}_{i}", _WIDTH) for i in range(2)]
        nodes = [
            _rand_pred(rng, 2, vars_)
            for _ in range(rng.randrange(1, 4))
        ]
        nodes = [n for n in nodes if n not in (T.TRUE, T.FALSE)]
        if not nodes:
            continue
        reset_blast_context()
        reset_word_tier()
        monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "1")
        status_on = get_blast_context().check(nodes, timeout_s=10.0)[0]
        reset_blast_context()
        reset_word_tier()
        monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
        status_off = get_blast_context().check(nodes, timeout_s=10.0)[0]
        monkeypatch.delenv("MYTHRIL_TPU_WORD_TIER")
        assert status_on == status_off, (trial, nodes)


def test_batch_check_states_parity_with_kill_switch(monkeypatch):
    """The frontier batch path returns compatible verdicts both ways:
    wherever both runs decide, they agree; lanes only the tier decides
    must match the oracle's answer."""
    from mythril_tpu.ops.batched_sat import batch_check_states

    x = T.var("bp", 256)
    sets = [
        [T.ult(x, T.const(5, 256)), T.ult(T.const(7, 256), x)],  # UNSAT
        [T.eq(x, T.const(5, 256))],                              # SAT
        [T.ult(x, T.const(100, 256))],                           # SAT
        [T.eq(T.bv_and(x, T.const(1, 256)), T.const(1, 256)),
         T.eq(T.bv_and(x, T.const(3, 256)), T.const(0, 256))],   # UNSAT
    ]
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "1")
    on = batch_check_states(list(sets))
    reset_blast_context()
    reset_word_tier()
    monkeypatch.setenv("MYTHRIL_TPU_WORD_TIER", "0")
    off = batch_check_states(list(sets))
    monkeypatch.delenv("MYTHRIL_TPU_WORD_TIER")
    assert on[0] is False and on[3] is False  # tier-decided UNSAT
    for a, b in zip(on, off):
        if a is not None and b is not None:
            assert a == b


def test_prune_infeasible_drops_word_unsat_states(monkeypatch):
    """laser/batch.py consults the tier: structurally-live states whose
    constraints are interval-UNSAT never reach a CDCL query."""
    from mythril_tpu.laser import batch as lb

    class _WS:
        def __init__(self, constraints):
            self.constraints = constraints

    class _State:
        def __init__(self, constraints):
            self.world_state = _WS(constraints)

    class _Constraints(list):
        @property
        def is_possible(self):
            raise AssertionError(
                "word tier should have decided this state"
            )

    x = T.var("pi", 256)
    dead = _Constraints(
        [T.ult(x, T.const(3, 256)), T.ult(T.const(9, 256), x)]
    )
    monkeypatch.setattr(
        "mythril_tpu.support.support_args.args.batched_solving", False
    )
    out = lb.prune_infeasible([_State(dead)])
    assert out == []


# ---------------------------------------------------------------------------
# hints -> blaster plumbing
# ---------------------------------------------------------------------------


def test_hint_literals_lowering():
    ctx = get_blast_context()
    x = T.var("hl", 8)
    ctx.blast_bits(x)  # register var bits
    bits = ctx.var_bits[x.id]
    lits = hint_literals(ctx, {x.id: (0b101, 0b001)})
    assert lits == [bits[0], -bits[2]]


def test_tightening_digest_stable_and_distinct():
    h1 = {3: (0xF0, 0x10), 9: (0x1, 0x1)}
    h2 = {9: (0x1, 0x1), 3: (0xF0, 0x10)}  # order must not matter
    h3 = {3: (0xF0, 0x20), 9: (0x1, 0x1)}
    assert tightening_digest(h1) == tightening_digest(h2)
    assert tightening_digest(h1) != tightening_digest(h3)
    assert tightening_digest(None) == 0 == tightening_digest({})


def test_cone_memo_keys_on_tightening():
    """An untightened memoized cone row must not serve a tightened
    query: the known-bits hint extends the ConeMemo key."""
    from mythril_tpu.ops.incremental import ConeMemo

    ctx = get_blast_context()
    x = T.var("cm", 8)
    lit = ctx.blast_lit(T.eq(x, T.const(3, 8)))
    bit0 = ctx.var_bits[x.id][0]
    memo = ConeMemo()
    plain = memo.cone(ctx, [lit])
    tight = memo.cone(ctx, [lit], known_bits=[bit0])
    assert len(memo) == 2  # distinct entries, no false hit
    # the tightened cone includes the hinted variable's bits
    assert set(plain[1].tolist()) <= set(tight[1].tolist())


def test_check_uses_hints_without_changing_verdicts():
    """A probe-resistant but hint-rich query still answers SAT through
    the funnel with the tier on (hints ride as implied assumptions)."""
    ctx = get_blast_context()
    x = T.var("ch", 256)
    # selector equation + a residue the word tier cannot decide
    sel = T.eq(T.lshr(x, T.const(224, 256)), T.const(0xCAFE, 256))
    res = T.eq(
        T.bv_and(T.mul(x, T.const(3, 256)), T.const(0xFF, 256)),
        T.const(0x99, 256),
    )
    status, env = ctx.check([sel, res], timeout_s=30.0)
    assert status == SatSolver.SAT
    value = env.variables[x.id]
    assert value >> 224 == 0xCAFE
    assert (value * 3) & 0xFF == 0x99


# ---------------------------------------------------------------------------
# lifecycle: resets, resume invalidation, memo scoping
# ---------------------------------------------------------------------------


def test_memo_reuse_and_generation_scoping():
    ctx = get_blast_context()
    x = T.var("gen", 256)
    nodes = [T.ult(x, T.const(4, 256)), T.ult(T.const(9, 256), x)]
    tier = get_word_tier()
    assert tier.decide(ctx, [nodes])[0][0] is False
    assert len(tier._memo) == 1
    # a NEW blast context (new generation) must not see stale verdicts
    reset_blast_context()
    ctx2 = get_blast_context()
    tier._sync_generation(ctx2.generation)
    assert len(tier._memo) == 0


def test_checkpoint_resume_invalidates_word_tier():
    """resume rebuilds the interner; reset_resident_pools (called by
    the checkpoint plane's restore path) must drop tier state too."""
    from mythril_tpu.ops.batched_sat import reset_resident_pools

    ctx = get_blast_context()
    x = T.var("cp", 256)
    tier = get_word_tier()
    tier.decide(ctx, [[T.ult(x, T.const(3, 256))]])
    assert tier._programs or tier._memo
    reset_resident_pools()
    assert not tier._memo
    assert not tier._programs
    assert tier._memo_generation == -1


def test_word_span_lands_in_phase_totals():
    """The tier's spans land in the analysis record's self seconds,
    tracing on or off."""
    from mythril_tpu.observability import spans

    spans.reset_for_tests()
    x = T.var("sp", 256)
    with spans.analysis("cli.analyze"):
        _decide_one([T.ult(x, T.const(3, 256)),
                     T.ult(T.const(9, 256), x)])
    (record,) = spans.analysis_profiles()
    word_s = sum(s for name, s in record["self_s"].items()
                 if name.startswith("word."))
    assert word_s > 0
    spans.reset_for_tests()
