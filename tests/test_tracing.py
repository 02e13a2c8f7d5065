"""What a boosting iteration tells a profiler and the counters (PR 28):
the build step's program and phase names, the device-side work counters of
the histogram launches against a hand count, the host span primitive, and
the vector-wise deferred counter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import profiling, telemetry
from lightgbm_tpu.config import config_from_params
from lightgbm_tpu.dataset import Dataset as RawDataset
from lightgbm_tpu.learner import rounds
from lightgbm_tpu.learner.fused import make_mesh
from lightgbm_tpu.ops.histogram import masked_hist_mxu_ops

SCOPES = ("root", "select", "partition", "tree_arrays", "feed", "hist",
          "exchange", "subtract", "split", "pack")
# what the compiler adds around the traced operations: no source, no scope
PLUMBING = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


def _problem(n=1200, f=8, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


def _compiled_build(mesh):
    X, y = _problem()
    cfg = config_from_params({"objective": "binary", "num_leaves": 31,
                              "min_data_in_leaf": 25, "verbose": -1})
    lr = rounds.RoundsTreeLearner(RawDataset(X, y, config=cfg), cfg, mesh)
    g = jnp.zeros(len(y), jnp.float32)
    mask, fmask = lr._masks(None)
    return lr._build.lower(
        lr.bins_dev, lr._rows_in(g), lr._rows_in(g), mask,
        lr.num_bins_dev, lr.is_cat_dev, fmask).compile().as_text()


def _computations(text):
    out, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m and not line.startswith(" "):
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    return out


def _while_body_instructions(text):
    """Every instruction of the build loop's body, the computations it
    calls included (fusions, conditional branches, nested loops).  The
    build loop is the largest loop of the program."""
    comps = _computations(text)

    def reach(root):
        seen, stack, found = set(), [root], []
        while stack:
            c = stack.pop()
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for line in comps[c]:
                stack += re.findall(
                    r"(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)", line)
                m = re.search(r"branch_computations=\{([^}]*)\}", line)
                if m:
                    stack += [x.strip().lstrip("%")
                              for x in m.group(1).split(",")]
                found.append(line)
        return found

    bodies = {m.group(1) for ins in comps.values() for line in ins
              for m in [re.search(r" while\(.*body=%?([\w.\-]+)", line)]
              if m}
    return max((reach(b) for b in bodies), key=len)


def test_build_step_is_named_and_scoped():
    text = _compiled_build(None)
    assert text.startswith("HloModule jit_build_tree_rounds,")
    total = scoped = 0
    for line in _while_body_instructions(text):
        opcode = re.search(r"= \S+ ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        # an instruction with no op_name is the compiler's own (layout
        # copies, broadcasts of constants): it has no source to name
        if name is None or (opcode and opcode.group(1) in PLUMBING):
            continue
        total += 1
        scoped += "lgbt." in name.group(1)
    assert total > 500
    assert scoped / total >= 0.9, (scoped, total)


def test_every_scope_is_in_the_sharded_build():
    """On a mesh the exchange and the closing reduction of the counters
    are operations too, so every scope of the list has something to name."""
    text = _compiled_build(make_mesh("data"))
    assert text.startswith("HloModule jit_build_tree_rounds")
    named = {scope for op_name in re.findall(r'op_name="([^"]*)"', text)
             for scope in re.findall(r"lgbt\.(\w+)", op_name)}
    assert named == set(SCOPES)


def test_gradients_program_is_named_after_the_objective():
    X, y = _problem(300, 4)
    bst = lgb.Booster({"objective": "binary", "verbose": -1,
                       "num_leaves": 4, "min_data_in_leaf": 5},
                      lgb.Dataset(X, y))
    obj, score = bst._gbdt.objective, bst._gbdt.train_score.score
    text = obj._f.lower(score, obj.label, obj.weights).compile().as_text()
    assert text.startswith("HloModule jit_gradients_binary,")


# ---- the work counters against a hand count ---------------------------------

def _hand_count(trees, n_rows):
    """rounds, launches, slots, live slots and operations of growing
    `trees` with num_leaves=7 on the XLA backend: one chunk of Kc=7
    slots (7 <= 8, the narrowest tier, so no tier is skipped), a launch
    a round after the root's, each for the round's splitting leaves."""
    F, B, Kc = 5, 256, 7
    rounds_, passes, slots, live, ops, rows = 0, 0, 0, 0, 0.0, 0.0
    for t in trees:
        # root: every row, one slot
        passes, slots, live = passes + 1, slots + 1, live + 1
        ops += 2.0 * n_rows * 3 * 1 * F * B
        rows += n_rows
        for split_leaves in t:
            rounds_ += 1
            passes, slots, live = passes + 1, slots + Kc, live + split_leaves
            ops += 2.0 * n_rows * 3 * Kc * F * B
            rows += n_rows
    return {"tree/rounds": rounds_, "tree/hist_passes": passes,
            "tree/hist_slots": slots, "tree/hist_live_slots": live,
            "tree/hist_mxu_ops": ops, "tree/hist_rows_touched": rows,
            # every round rewrites every row's leaf id
            "tree/partition_rows": rounds_ * n_rows}


def _rounds_of(tree):
    """Leaves split per round of a grown tree, from its node depths:
    the rounds learner splits, in round r, exactly the internal nodes
    of depth r."""
    k = tree.num_leaves - 1
    depth = np.zeros(k, int)
    for node in range(k):
        for child in (tree.left_child[node], tree.right_child[node]):
            if child >= 0:
                depth[child] = depth[node] + 1
    return [int((depth == r).sum()) for r in range(depth.max() + 1)]


def test_work_counters_match_a_hand_count():
    X, y = _problem(600, 5, seed=0)
    profiling.reset()
    bst = lgb.Booster({"objective": "binary", "verbose": -1, "num_leaves": 7,
                       "min_data_in_leaf": 5, "tree_growth": "rounds"},
                      lgb.Dataset(X, y))
    for _ in range(2):
        bst.update()
    got = profiling.counters("tree/")
    bst._gbdt._flush_pending()
    trees = [_rounds_of(t) for t in bst._gbdt.models]
    assert all(sum(t) == 6 for t in trees)                   # 7 leaves
    want = _hand_count(trees, 600)
    assert {k: got[k] for k in want} == want
    assert got["tree/hist_live_slots"] == 2 * 7
    profiling.reset()


def test_mxu_ops_of_a_pallas_launch_from_its_shapes():
    """The chip's launches at the Epsilon cell's shapes, by hand: int32
    bins in groups of 8 columns, 3K value rows padded to 8, rows padded
    to the 8192-row chunk, 256 bins; 63 bins pack two columns into 128
    lanes; int8-stored bins take groups of 32 columns and 2048 rows."""
    kw = dict(backend="pallas", input_dtype="int8")
    full = masked_hist_mxu_ops(2000, 200064, 84, bins_itemsize=4,
                               num_bins_padded=256, max_num_bin=255, **kw)
    assert full == 2.0 * 204800 * 256 * 2000 * 256
    root = masked_hist_mxu_ops(2000, 400000, 1, bins_itemsize=4,
                               num_bins_padded=256, max_num_bin=255, **kw)
    assert root == 2.0 * 401408 * 8 * 2000 * 256
    b63 = masked_hist_mxu_ops(2000, 200064, 84, bins_itemsize=4,
                              num_bins_padded=128, max_num_bin=63, **kw)
    assert b63 == 2.0 * 204800 * 256 * 1000 * 128
    narrow = masked_hist_mxu_ops(28, 4096, 8, bins_itemsize=1,
                                 num_bins_padded=256, max_num_bin=255, **kw)
    assert narrow == 2.0 * 4096 * 24 * 32 * 256
    xla = masked_hist_mxu_ops(28, 1000, 8, bins_itemsize=4,
                              num_bins_padded=256, backend="xla",
                              input_dtype="float32")
    assert xla == 2.0 * 1000 * 24 * 28 * 256


# ---- the host span primitive ------------------------------------------------

class _Annotations:
    """Stands in for jax.profiler.TraceAnnotation: records what is opened."""
    def __init__(self):
        self.opened = []

    def __call__(self, name, **attrs):
        self.opened.append((name, attrs))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_phase_is_a_trace_annotation_and_times_only_under_telemetry(
        monkeypatch, tmp_path):
    seen = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    profiling.reset()
    assert not telemetry.enabled()
    with profiling.phase("tree"):
        pass
    with profiling.phase("update", iteration=3):
        pass
    assert seen.opened == [("lgbt.tree", {}),
                           ("lgbt.update", {"iteration": 3})]
    assert profiling.timings() == {}
    with profiling.phase("serve/execute", force=True):
        pass
    assert set(profiling.timings()) == {"serve/execute"}
    assert len(seen.opened) == 2         # a forced phase opens no span
    telemetry.configure(str(tmp_path / "spans.jsonl"))
    try:
        with profiling.phase("tree"):
            pass
        assert profiling.timings()["tree"] > 0
        assert seen.opened[-1] == ("lgbt.tree", {})
    finally:
        telemetry.reset()
        profiling.reset()
    with profiling.phase("tree"):
        pass
    assert profiling.timings() == {}


def test_an_iteration_opens_the_spans_of_every_layer(monkeypatch):
    seen = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    X, y = _problem(300, 4)
    bst = lgb.Booster({"objective": "binary", "verbose": -1, "num_leaves": 4,
                       "min_data_in_leaf": 5, "tree_growth": "rounds"},
                      lgb.Dataset(X, y))
    bst.update()
    bst.update()
    names = [n for n, _ in seen.opened]
    second = names[names.index("lgbt.update", 1):]
    assert second[:3] == ["lgbt.update", "lgbt.collect_tree",
                          "lgbt.wait_device"]
    assert second[3:] == ["lgbt.boosting", "lgbt.bagging", "lgbt.tree",
                          "lgbt.score"]
    assert [a for n, a in seen.opened if n == "lgbt.update"] == [
        {"iteration": 0}, {"iteration": 1}]
    bst._gbdt.eval_train()
    assert seen.opened[-1][0] == "lgbt.metric"


def test_a_served_predict_times_its_phase_and_opens_no_span(monkeypatch):
    """The serving `/stats` phases are accumulators only: a predict call
    pays no TraceAnnotation, and no `lgbt.serve/*` span exists."""
    from lightgbm_tpu.serving.runtime import PredictorRuntime
    X, y = _problem(300, 4)
    bst = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 4,
                     "min_data_in_leaf": 5}, lgb.Dataset(X, y),
                    num_boost_round=2)
    rt = PredictorRuntime(bst, max_batch_rows=64, min_bucket_rows=16)
    seen = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    profiling.reset()
    assert not telemetry.enabled()
    np.testing.assert_allclose(rt.predict(X[:20]), bst.predict(X[:20]),
                               atol=1e-6)
    assert profiling.timings()["serve/execute"] > 0
    assert seen.opened == []


# ---- the vector-wise deferred counter ---------------------------------------

def test_count_deferred_vector_drains_like_the_per_name_path(monkeypatch):
    names = ("vec/a", "vec/b", "vec/c")
    vecs = [jnp.asarray([1.0, 10.0, 100.0]), jnp.asarray([2.0, 20.0, 200.0]),
            jnp.asarray([3.0, 30.0, 300.0])]
    profiling.reset()
    for v in vecs:                       # the path it replaces
        for i, n in enumerate(names):
            profiling.count_deferred((n,), v[i:i + 1])
    per_name = profiling.counters("vec/")

    profiling.reset()
    fetches = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetches.append(1) or real_get(x))
    for v in vecs:
        profiling.count_deferred(names, v)
    assert fetches == []                              # no sync on the way
    assert profiling.counters_nosync("vec/") == {}    # nor on this read
    assert len(profiling._deferred) == 1              # one live buffer
    assert isinstance(profiling._deferred[(names, None)][0], jax.Array)
    assert profiling.counters("vec/") == per_name == {
        "vec/a": 6.0, "vec/b": 60.0, "vec/c": 600.0}
    assert fetches == [1]                             # one fetch, at the drain
    assert profiling.counter_value("vec/b") == 60.0
    assert fetches == [1]                             # nothing left pending
    profiling.reset()


def test_count_deferred_folds_on_the_host_at_the_drain():
    """A counter that is a function of what the vector counts: computed
    from the fetched totals and the number of calls, no device work."""
    seen = []

    def fold(totals, calls):
        seen.append((list(map(float, totals)), calls))
        return (("vec/twice_b_less_calls", 2.0 * totals[1] - calls),)

    profiling.reset()
    for v in ([1.0, 10.0], [2.0, 20.0], [3.0, 30.0]):
        profiling.count_deferred(("vec/a", "vec/b"), jnp.asarray(v), fold)
    assert seen == [] and len(profiling._deferred) == 1
    assert profiling.counters("vec/") == {
        "vec/a": 6.0, "vec/b": 60.0, "vec/twice_b_less_calls": 117.0}
    assert seen == [([6.0, 60.0], 3)]
    profiling.reset()


def test_the_learner_feeds_every_stats_counter_as_one_vector():
    assert set(rounds.STATS_COUNTERS) <= set(profiling.CANONICAL_COUNTERS)
    assert len(rounds.STATS_COUNTERS) == 12
    X, y = _problem(300, 4)
    profiling.reset()
    bst = lgb.Booster({"objective": "binary", "verbose": -1, "num_leaves": 4,
                       "min_data_in_leaf": 5, "tree_growth": "rounds"},
                      lgb.Dataset(X, y))
    bst.update()
    (names, fold), = list(profiling._deferred)
    assert names == rounds.STATS_COUNTERS
    # what is static per pass rides no slot: folded on the host
    assert fold.func is rounds.search_counters
    assert profiling._deferred[(names, fold)][0].shape == (12,)
    got = profiling.counters("tree/")
    assert set(got) >= set(rounds.STATS_COUNTERS)
    # the benchmark's feed_rows_per_iter reads this name; no launch
    # copies a row, and the vector has no slot for it
    assert got[profiling.FEED_ROWS] == 0
    assert profiling.FEED_ROWS not in rounds.STATS_COUNTERS
    # one device launches no collective
    assert got[profiling.EXCHANGE_COLLECTIVES] == 0
    assert got[profiling.HIST_EXCHANGE_BYTES] == 0
    profiling.reset()
