"""repro.design tests: operator registry round-trip, SearchStrategy
protocol + anneal parity vs the pre-refactor golden walk, cache-key
strategy coverage, PlanStore.suggest, per-shard seed divergence."""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

import repro
import repro.design
from repro.core.matrices import (banded_matrix, hyb_friendly_matrix,
                                 powerlaw_matrix, random_uniform_matrix)
from repro.core.search import (AlphaSparseSearch, DesignSpace, ProgramCache,
                               SearchConfig, run_search)
from repro.design.registry import GraphError, unregister_operator
from repro.design.strategies import (AnnealStrategy, CostModelGuidedStrategy,
                                     GridStrategy, make_strategy)

DATA = Path(__file__).parent / "data"


# --------------------------- registry round-trip ----------------------------

@pytest.fixture
def row_reverse_op():
    """A custom out-of-tree operator registered for the duration of a
    test: a row-reversal permute (same shape as the reordering operators
    a real extension would add)."""

    @repro.design.register_operator("TEST_ROW_REVERSE")
    class RowReverse(repro.design.Operator):
        stage = repro.design.STAGE_CONVERTING

        @staticmethod
        def applicable(meta):
            return meta.compressed and len(meta.blocks) == 1

        @staticmethod
        def apply(meta, spec):
            b = meta.blocks[0]
            n = b.n_block_rows
            new_rows = (n - 1 - b.rows).astype(np.int32)
            order = np.lexsort((b.cols, new_rows))
            block = dataclasses.replace(
                b, row_ids=np.ascontiguousarray(b.row_ids[::-1]),
                rows=new_rows[order], cols=b.cols[order],
                vals=b.vals[order])
            return meta.with_blocks([block], spec.label())

    yield RowReverse
    unregister_operator("TEST_ROW_REVERSE")


def _custom_graph():
    mk = repro.OpSpec.make
    return repro.OperatorGraph.chain(
        mk("COMPRESS"), mk("TEST_ROW_REVERSE"),
        mk("TILE_ROW_BLOCK", rows=32), mk("LANE_ROW_BLOCK"),
        mk("LANE_TOTAL_RED", combine="scatter"))


def test_custom_operator_compiles_saves_loads_bit_exact(
        small_irregular, row_reverse_op, tmp_path):
    """Acceptance: a custom operator registered outside src/repro compiles,
    saves, loads, and matches the dense oracle without any edit to core."""
    m = small_irregular
    plan = repro.compile(m, repro.Target(), graph=_custom_graph())
    assert "TEST_ROW_REVERSE" in plan.graph.op_names()

    x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
    oracle = m.spmv_dense_oracle(x)
    y = np.asarray(plan(x))
    scale = np.abs(oracle).max() + 1e-30
    np.testing.assert_allclose(y, oracle, atol=1e-4 * scale, rtol=0)

    path = tmp_path / "custom.plan.npz"
    plan.save(path)
    loaded = repro.SpmvPlan.load(path)
    assert np.array_equal(np.asarray(loaded(x)), y)          # bit-exact
    assert loaded.graph.op_names() == plan.graph.op_names()  # graph JSON


def test_custom_operator_enters_design_space(small_irregular, row_reverse_op):
    space = DesignSpace(small_irregular, SearchConfig())
    assert any("TEST_ROW_REVERSE" in s.converting
               for s in space.structures())


def test_design_space_parity_without_custom_ops(small_irregular):
    """With only built-ins registered the space equals the baseline tables
    (the strategy-parity precondition)."""
    from repro.design.space import (CONVERTING_CHOICES, MAPPING_IMPL_CHOICES,
                                    _registry_extra_choices)
    extra_convs, extra_chains = _registry_extra_choices()
    assert extra_convs == () and extra_chains == ()
    cfg = dataclasses.replace(SearchConfig(), use_pruning=False)
    space = DesignSpace(small_irregular, cfg)
    n_mix = 4  # branch-mix structures appended by structure_space
    assert len(space.structures()) == (len(CONVERTING_CHOICES)
                                       * len(MAPPING_IMPL_CHOICES) + n_mix)


def test_unregistered_operator_raises_clear_graph_error(small_uniform):
    mk = repro.OpSpec.make
    g = repro.OperatorGraph(
        converting=(mk("COMPRESS"), mk("NO_SUCH_OP")),
        branch_chains=((mk("LANE_ROW_BLOCK"), mk("LANE_TOTAL_RED")),))
    with pytest.raises(GraphError, match="NO_SUCH_OP.*registry"):
        g.validate()
    with pytest.raises(GraphError, match="register_operator"):
        from repro.core.graph import run_graph
        run_graph(small_uniform, g)


# ------------------------- strategy protocol + parity -----------------------

GOLDEN_FAMILIES = {
    "banded": lambda: banded_matrix(300, 3, seed=12),
    "uniform": lambda: random_uniform_matrix(256, 256, 0.02, seed=13),
    "powerlaw": lambda: powerlaw_matrix(400, 350, 6.0, 1.0, seed=11),
    "hyb_like": lambda: hyb_friendly_matrix(256, 4, 8, 64, seed=7),
}

# choice-free determinism: coarse_samples exceeds every coarse bind size,
# so the explored sequence is a pure function of (matrix, seed) — it
# cannot depend on machine timing (the golden was captured pre-refactor)
PARITY_CFG = dict(max_seconds=600.0, coarse_samples=100,
                  use_cost_model=False, timing_repeats=1, seed=0)


@pytest.mark.parametrize("family", sorted(GOLDEN_FAMILIES))
def test_anneal_parity_with_prerefactor_walk(family):
    """The extracted AnnealStrategy replays the pre-refactor search walk
    candidate-for-candidate on the 4 tier-1 families at fixed seed (golden
    captured from the monolithic run_search before the repro.design
    split). The winner is the argmin over this identical candidate set,
    so winner identity follows up to timing noise — which flipped winners
    between *identical pre-refactor runs* too."""
    golden = json.loads(
        (DATA / "golden_anneal_walk_small.json").read_text())[family]
    s = AlphaSparseSearch(GOLDEN_FAMILIES[family](),
                          SearchConfig(max_structures=2, **PARITY_CFG))
    res = s.run()     # default strategy = AnnealStrategy
    assert [g.label() for g in s._memo] == golden["sequence"]
    assert res.n_structures == golden["n_structures"]
    assert res.n_evaluations == golden["n_evaluations"]
    assert res.best_graph.label() in golden["sequence"]
    assert res.strategy_name == "anneal"


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(GOLDEN_FAMILIES))
def test_anneal_parity_full_walk(family):
    """Nightly: the longer pre-refactor golden walk (5 structures)."""
    golden = json.loads(
        (DATA / "golden_anneal_walk.json").read_text())[family]
    s = AlphaSparseSearch(GOLDEN_FAMILIES[family](),
                          SearchConfig(max_structures=5, **PARITY_CFG))
    res = s.run(AnnealStrategy())
    assert [g.label() for g in s._memo] == golden["sequence"]
    assert res.n_evaluations == golden["n_evaluations"]


TINY = SearchConfig(max_seconds=10, max_structures=2, coarse_samples=2,
                    fine_top_structures=1, fine_eval_budget=1,
                    timing_repeats=1, seed=3)


def test_grid_strategy_runs_and_is_deterministic(small_uniform):
    # fine_eval_budget=0: the coarse grid is timing-independent, so two
    # runs explore the identical candidate set
    cfg = dataclasses.replace(TINY, fine_eval_budget=0)
    r1 = run_search(small_uniform, cfg, strategy="grid")
    r2 = run_search(small_uniform, cfg, strategy=GridStrategy())
    assert r1.strategy_name == "grid"
    # grid is rng-free: identical candidate sets both runs
    assert [r.graph for r in r1.records] == [r.graph for r in r2.records]
    assert math.isfinite(r1.best_seconds)


def test_cost_model_strategy_runs(small_uniform):
    cfg = dataclasses.replace(TINY, coarse_samples=3)
    res = run_search(small_uniform, cfg,
                     strategy=CostModelGuidedStrategy(rounds=1, pool=8))
    assert res.strategy_name == "cost_model"
    assert math.isfinite(res.best_seconds)
    # ranked (model-phase) proposals were actually evaluated
    assert res.n_evaluations > 0


def test_make_strategy_resolution():
    assert isinstance(make_strategy(None), AnnealStrategy)
    assert isinstance(make_strategy("grid"), GridStrategy)
    assert isinstance(make_strategy(GridStrategy), GridStrategy)
    s = AnnealStrategy(temperature=0.9)
    assert make_strategy(s) is s
    with pytest.raises(ValueError, match="unknown search strategy"):
        make_strategy("nope")


def test_register_custom_strategy(small_uniform):
    from repro.design.strategies import (Proposal, STRATEGY_REGISTRY,
                                         SearchStrategy, register_strategy)

    @register_strategy("test_first_seed")
    class FirstSeedOnly(SearchStrategy):
        def reset(self, space, rng, config, deadline=None):
            self._done = False

        def propose(self, space, history):
            if self._done:
                return []
            self._done = True
            s = space.seed_structures()[0]
            return [Proposal(g, s.label(), mandatory=True)
                    for g in space.bind(s, "coarse")]

    try:
        res = run_search(small_uniform, TINY, strategy="test_first_seed")
        assert res.strategy_name == "test_first_seed"
        assert math.isfinite(res.best_seconds)
    finally:
        STRATEGY_REGISTRY.pop("test_first_seed", None)


# ------------------------ cache keys cover the strategy ---------------------

def test_program_cache_key_covers_strategy(small_uniform):
    cfg = SearchConfig()
    k_anneal = ProgramCache.key(small_uniform, cfg, None)
    assert k_anneal == ProgramCache.key(small_uniform, cfg, AnnealStrategy())
    assert k_anneal != ProgramCache.key(small_uniform, cfg, "grid")
    assert k_anneal != ProgramCache.key(small_uniform, cfg,
                                        AnnealStrategy(temperature=0.9))
    assert (ProgramCache.key(small_uniform, cfg, "grid")
            != ProgramCache.key(small_uniform, cfg, "cost_model"))


def test_program_cache_no_cross_strategy_hit(small_uniform):
    cache = ProgramCache()
    res = run_search(small_uniform, TINY, cache=cache, strategy="grid")
    assert cache.get(small_uniform, TINY, "grid") is res
    # an anneal request must MISS the grid entry for the same matrix/budget
    assert cache.get(small_uniform, TINY) is None
    assert cache.get(small_uniform, TINY, AnnealStrategy()) is None


def test_plan_store_key_covers_strategy(small_uniform):
    t = repro.Target()
    k = repro.PlanStore.key(small_uniform, t, 5.0)
    assert k != repro.PlanStore.key(small_uniform, t, 5.0, strategy="grid")
    # explicit-graph plans have no strategy component (no search ran)
    g = _seed_graph()
    assert (repro.PlanStore.key(small_uniform, t, None, g)
            == repro.PlanStore.key(small_uniform, t, None, g, "grid"))


def _seed_graph():
    mk = repro.OpSpec.make
    return repro.OperatorGraph.chain(
        mk("COMPRESS"), mk("TILE_ROW_BLOCK", rows=32),
        mk("LANE_ROW_BLOCK"), mk("LANE_TOTAL_RED", combine="scatter"))


# ------------------------------ PlanStore.suggest ---------------------------

def test_plan_store_suggest_nearest_and_warm_start(tmp_path):
    store = repro.PlanStore(tmp_path)
    m1 = random_uniform_matrix(256, 256, 0.02, seed=13)
    assert store.suggest(m1) is None                    # empty store

    g = _seed_graph()
    repro.compile(m1, repro.Target(), graph=g, store=store)
    # same statistics family -> the stored winning graph comes back
    m2 = random_uniform_matrix(260, 256, 0.02, seed=5)
    suggestion = store.suggest(m2)
    assert suggestion is not None
    assert suggestion.op_names() == g.op_names()
    # wildly different statistics -> no suggestion within max_distance
    m3 = powerlaw_matrix(40000, 350, 3.0, 0.6, seed=2)
    assert store.suggest(m3, max_distance=0.05) is None

    # warm start end to end: the suggested graph is timed first ("warm"
    # record) and competes for the win
    cfg = dataclasses.replace(TINY, max_structures=0, use_cost_model=False)
    res = run_search(m2, cfg, warm_start=[suggestion])
    assert any(r.structure == "warm" for r in res.records)
    assert math.isfinite(res.best_seconds)


def test_compile_with_store_auto_warm_starts(tmp_path):
    store = repro.PlanStore(tmp_path)
    m1 = random_uniform_matrix(256, 256, 0.02, seed=13)
    repro.compile(m1, repro.Target(), graph=_seed_graph(), store=store)
    m2 = random_uniform_matrix(260, 256, 0.02, seed=5)
    cfg = dataclasses.replace(TINY, max_structures=0, use_cost_model=False)
    plan = repro.compile(m2, repro.Target(), budget=cfg, store=store)
    res = plan.search_result
    assert res is not None
    assert any(r.structure == "warm" for r in res.records)


def test_grid_strategy_ignores_warm_pseudo_structure(small_uniform):
    """A store-suggested warm start must not eat fine_top_structures
    slots: 'warm' matches no structure.label() in the fine phase."""
    cfg = dataclasses.replace(TINY, max_structures=1, fine_top_structures=1)
    s = AlphaSparseSearch(small_uniform, cfg)
    strat = GridStrategy()
    res = s.run(strat, warm_start=[_seed_graph()])
    assert any(r.structure == "warm" for r in res.records)
    # the warm candidate was timed but never entered the per-structure
    # table, so it cannot claim a fine_top_structures slot
    assert "warm" not in strat._by
    assert len(strat._by) == 5          # 4 seeds + max_structures=1


def test_plan_store_survives_corrupt_entry(tmp_path):
    store = repro.PlanStore(tmp_path)
    m = random_uniform_matrix(256, 256, 0.02, seed=13)
    t = repro.Target()
    g = _seed_graph()
    repro.compile(m, t, graph=g, store=store)
    # truncate the stored plan: get() must warn and recompile, not raise
    path = store._path(store.key(m, t, None, g))
    path.write_bytes(path.read_bytes()[:40])
    with pytest.warns(RuntimeWarning, match="unusable"):
        plan = repro.compile(m, t, graph=g, store=store)
    x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
    assert np.isfinite(np.asarray(plan(x))).all()


# ------------------------- per-shard seed divergence ------------------------

def test_dist_search_derives_distinct_per_shard_seeds(monkeypatch):
    """dist_search must hand every shard a different SearchConfig.seed
    (seed + shard_id) — identical seeds would make all shards explore
    the same walk."""
    from repro.dist import search as dsearch

    from repro.core.graph import run_graph
    from repro.core.kernel_builder import build_program
    from repro.core.search import SearchResult
    from repro.dist.spmv import default_shard_graph

    m = powerlaw_matrix(400, 400, 6.0, 1.0, seed=9)
    seen = []

    def spy(matrix, cfg, cache=None, strategy=None, warm_start=None):
        # record the derived per-shard seed; return a cheap valid result
        # (no real search — this test is about the seed plumbing)
        seen.append(cfg.seed)
        g = default_shard_graph(matrix)
        prog = build_program(run_graph(matrix, g), jit=False)
        return SearchResult(best_graph=g, best_program=prog,
                            best_seconds=1e-3, gflops=1.0, n_evaluations=1,
                            n_structures=1, wall_seconds=0.0, records=[],
                            cost_model_mad=None, pruned_ops=())

    monkeypatch.setattr(dsearch, "run_search", spy)

    class FakeMesh:             # only _axis_size reads .shape
        shape = {"data": 2}

    cfg = dsearch.ShardedSearchConfig(
        search=SearchConfig(max_seconds=5, max_structures=1,
                            coarse_samples=1, fine_eval_budget=0,
                            timing_repeats=1, use_cost_model=False, seed=7),
        min_nnz_for_search=1)
    try:
        dsearch.dist_search(m, FakeMesh(), cfg)
    except Exception:
        pass   # building the sharded program may reject the fake mesh —
               # the per-shard searches (what we spy on) already ran
    assert len(seen) == 2
    assert seen[0] != seen[1]
    assert seen == [7, 8]       # cfg.seed + search.seed + shard_id


def test_shard_walks_diverge_under_derived_seeds(small_uniform):
    """Different derived seeds shuffle the structure space differently:
    the annealed walk (post-seed-pass) diverges between shards."""
    cfg = SearchConfig(max_seconds=600.0, max_structures=3,
                       coarse_samples=100, use_cost_model=False,
                       timing_repeats=1)
    orders = []
    for seed in (7, 8):
        space = DesignSpace(small_uniform,
                            dataclasses.replace(cfg, seed=seed))
        strat = AnnealStrategy()
        strat.reset(space, np.random.default_rng(seed), cfg)
        orders.append([s.label() for s in strat._queue])
    assert orders[0][:4] == orders[1][:4]      # same mandatory seed pass
    assert orders[0] != orders[1]              # diverging walk after it


def test_equal_programs_compile_once():
    """GRID_ACC and scatter on an affine rowmap build the same program:
    the search compiles and times it once and gives both graphs its time."""
    from repro.core.graph import OperatorGraph
    from repro.core.operators import OpSpec
    m = banded_matrix(96, 2, seed=3)

    def ell(combine):
        return OperatorGraph.chain(
            OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
            OpSpec.make("LANE_ROW_BLOCK"),
            OpSpec.make("LANE_TOTAL_RED", combine=combine))

    s = AlphaSparseSearch(m, SearchConfig(backend="pallas", seed=0))
    t_scatter = s._evaluate(ell("scatter"), "ell")
    t_grid = s._evaluate(ell("grid_acc"), "ell")
    assert t_scatter == t_grid and np.isfinite(t_grid)
    assert len(s._by_program) == 1 and len(s._memo) == 2
    assert len(s.records) == 2
