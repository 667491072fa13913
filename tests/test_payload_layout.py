"""The sorted payload is word-major (``[SW, N + W]``) and grows the forest
of the commit that held it row-major.

``tests/data/payload_layout_golden.json`` was recorded from the PARENT of
PR 36 (payload ``[N + W, SW]``: the split loop's carry and the copy-back
loop row-major, the partition and histogram loops N-minor, and a
whole-buffer conversion between them twice a split at 57 words). The
payload's shape is all that changed: the same rows reach the same windows
in the same order, so the model text, every tree's split features,
threshold bins and leaf row counts, and the last tree's row -> leaf map
stay what they were, for both bin widths, at ``higgs``'s 9 words a row and
at ``istella-s``'s 57, with float gradients, quantized ones and a bagging
mask column, and for the data-parallel learner, whose shards hold the
payload's rows on its second axis now. Three more cases hold the readers of
the carried histogram state besides the split itself to the forests of the
commit before ``ops.histogram.write_children``: a forced split gathers its
statistics from a leaf's histogram, the intermediate monotone method
re-scans the leaves whose bounds a split tightened, and the voting learner
votes over its shards' histograms. The golden holds a sha256 of each
(as ``row_leaf`` and the model text always were), and the integers of one
case, ``IN_THE_CLEAR``, beside their digests, to debug a miss from.

Windows are forced to 1,024 rows over 3,000-row datasets, so leaves span
several windows. The integers hold on any machine. The model text is
floats printed in full, and the ``gather`` layout (which never builds the
payload) grew the same text as the sorted one on the parent: where a
machine rounds a float sum otherwise than the recording one did, the
gather text misses the recorded digest too, and the sorted text is then
held to the gather text of the same process (``tests/test_layout.py``'s
contract).
Re-record (only from a commit whose payload is trusted):
``python tests/test_payload_layout.py --record``.
"""
import hashlib
import json
import os

import numpy as np
import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "payload_layout_golden.json")
N = 3000
ROUNDS = 3

# what rides a row beside its bins, as bin-dtype columns (uint8, uint16):
# two float32 gradients; two int8 levels; the gradients and a mask column
RIDERS = {"plain": (8, 4), "quant": (2, 1), "bagged": (9, 5)}
EXTRA = {
    "plain": {},
    "quant": {"use_quantized_grad": True, "num_grad_quant_bins": 16,
              "stochastic_rounding": False},
    "bagged": {"bagging_fraction": 0.6, "bagging_freq": 1},
}
CASES = [(bins, words, channel) for bins in ("uint8", "uint16")
         for words in (9, 57) for channel in RIDERS]
# readers of the carried histogram state, with float gradients riding
FORCED = os.path.join(os.path.dirname(__file__), "data",
                      "payload_forced_splits.json")
READERS = {
    "forced": {"forcedsplits_filename": FORCED},
    "intermediate": {"monotone_constraints_method": "intermediate"},
    "voting": {"tree_learner": "voting", "tpu_num_devices": 4},
}
RIDERS.update(dict.fromkeys(READERS, RIDERS["plain"]))
EXTRA.update(READERS)
CASES += [("uint8", 57, reader) for reader in READERS]
INTEGERS = ("split_feature", "threshold_bin", "leaf_count")
IN_THE_CLEAR = "uint8-57-plain"


def _features(bins: str, words: int, channel: str) -> int:
    """Feature count whose packed row is exactly ``words`` u32 words."""
    per_word = 4 if bins == "uint8" else 2
    return words * per_word - RIDERS[channel][bins == "uint16"]


def _data(features: int):
    rng = np.random.RandomState(11)
    X = rng.randn(N, features)
    y = X[:, 1] + np.sin(X[:, 2] * 2) + X[:, 3] * 0.5 + 0.1 * rng.randn(N)
    return X, y


def _monotone(features: int) -> list:
    """Increasing in the two linear features of ``_data``, decreasing in
    the sine's."""
    return [0, 1, -1, 1] + [0] * (features - 4)


def _params(bins: str, channel: str, layout: str, **more) -> dict:
    return {"objective": "regression", "num_leaves": 15,
            "min_data_in_leaf": 5, "learning_rate": 0.1, "verbose": -1,
            "max_bin": 31 if bins == "uint8" else 300,
            "enable_bundle": False, "tpu_fused_learner": "1",
            "tpu_hist_impl": "onehot", "tree_layout": layout,
            **EXTRA[channel], **more}


def _grow(features: int, params: dict, learner_type: str) -> dict:
    import jax

    import lambdagap_tpu as lgb
    X, y = _data(features)
    gb = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                   num_boost_round=ROUNDS)._booster
    learner = gb.learner
    assert type(learner).__name__ == learner_type
    assert learner.layout == params["tree_layout"]
    text = gb.save_model_to_string()
    text = text[:text.index("end of trees")]
    row_leaf = np.asarray(jax.device_get(learner.last_row_leaf), np.int32)
    return {
        "learner": learner,
        "split_feature": [list(map(int, t.split_feature_inner))
                          for t in gb.host_models],
        "threshold_bin": [list(map(int, t.threshold_bin))
                          for t in gb.host_models],
        "leaf_count": [list(map(int, t.leaf_count[:t.num_leaves]))
                       for t in gb.host_models],
        "row_leaf_sha256": hashlib.sha256(
            np.ascontiguousarray(row_leaf).tobytes()).hexdigest(),
        "model_text_sha256": hashlib.sha256(text.encode()).hexdigest(),
    }


def _serial(bins: str, words: int, channel: str, layout: str) -> dict:
    features = _features(bins, words, channel)
    more = ({"monotone_constraints": _monotone(features)}
            if channel == "intermediate" else {})
    voting = channel == "voting"
    got = _grow(features, _params(bins, channel, layout, **more),
                "FusedVotingParallelTreeLearner" if voting
                else "FusedTreeLearner")
    learner = got["learner"]
    assert str(learner.hx_rows.dtype) == bins
    assert learner.n_loc == N // 4 if voting else learner._window(N) == 1024
    if channel == "forced":
        assert [t[0] for t in got["split_feature"]] == [7] * ROUNDS
    return got


def _data_parallel(layout: str) -> dict:
    """Four shards of 750 rows, 57 words a row, quantized (the mode whose
    trees do not depend on the number of shards)."""
    got = _grow(_features("uint8", 57, "quant"),
                _params("uint8", "quant", layout, tree_learner="data",
                        tpu_num_devices=4),
                "FusedDataParallelTreeLearner")
    assert got["learner"].n_loc == N // 4
    return got


def _small_window(self) -> int:
    return 1024


@pytest.fixture
def small_windows(monkeypatch):
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    monkeypatch.setattr(FusedTreeLearner, "_pick_chunk", _small_window)


def _payload_shape(learner, has_mask: bool):
    """Shape of the payload the learner's layout program builds."""
    import jax
    import jax.numpy as jnp
    n = learner.hx_rows.shape[0]
    q = jnp.zeros(n if learner.quant else 1, jnp.int8)
    return jax.eval_shape(
        lambda *a: learner._build_sorted_impl(*a, has_mask=has_mask),
        jnp.zeros(n), jnp.ones(n), jnp.ones(n, bool), learner.hx_rows, q,
        q).shape


def _sha256(trees: list) -> str:
    """Digest of one integer array a tree, as compact JSON."""
    return hashlib.sha256(
        json.dumps(trees, separators=(",", ":")).encode()).hexdigest()


def _golden(name: str, got: dict) -> dict:
    """What the golden file keeps of a grown forest."""
    kept = {f"{key}_sha256": _sha256(got[key]) for key in INTEGERS}
    kept.update({key: got[key] for key in
                 ("row_leaf_sha256", "model_text_sha256")})
    if name == IN_THE_CLEAR:
        kept.update({key: got[key] for key in INTEGERS})
    return kept


def _held_to_golden(name: str, grow) -> None:
    with open(GOLDEN) as f:
        want = json.load(f)[name]
    got = grow("sorted")
    assert max(len(c) for c in got["leaf_count"]) > 8, "trees did not grow"
    assert (name == IN_THE_CLEAR) == ("leaf_count" in want)
    kept = _golden(name, got)
    text = want.pop("model_text_sha256")
    # the integers in the clear first: a miss there shows what moved
    for key in sorted(want, key=lambda k: k.endswith("_sha256")):
        assert kept[key] == want[key], key
    if got["model_text_sha256"] != text:
        gather = grow("gather")["model_text_sha256"]
        assert gather != text, "the sorted payload's model text left the parent's"
        assert got["model_text_sha256"] == gather


@pytest.mark.parametrize("bins,words,channel", CASES)
def test_forest_equals_the_row_major_payloads(small_windows, bins, words,
                                              channel):
    def grow(layout):
        got = _serial(bins, words, channel, layout)
        if layout == "sorted" and channel != "voting":
            assert _payload_shape(got["learner"], channel == "bagged") \
                == (words, N + 1024)
        return got
    _held_to_golden(f"{bins}-{words}-{channel}", grow)


def test_data_parallel_forest_equals_the_row_major_payloads(small_windows):
    _held_to_golden("data_parallel", _data_parallel)


def test_sorted_mesh_learner_holds_no_column_major_matrix(small_windows):
    """Under the sorted layout the program decodes the split feature from
    the payload window: a ``[C, N]`` copy on the mesh is dead bytes, and
    the placeholder still shards over the data axis (one column a shard)."""
    learner = _data_parallel("sorted")["learner"]
    assert learner.x_cols.shape == (1, 4)
    assert [s.data.shape for s in learner.x_cols.addressable_shards] \
        == [(1, 1)] * 4
    import jax
    big = [a.shape for a in learner.__dict__.values()
           if isinstance(a, jax.Array) and a.ndim == 2
           and a.shape[0] < a.shape[1] and a.shape[1] >= N]
    assert big == [], big


def _write_golden(out: dict) -> None:
    """One case a line."""
    lines = [f" {json.dumps(name)}: "
             + json.dumps(out[name], separators=(",", ":"), sort_keys=True)
             for name in sorted(out)]
    with open(GOLDEN, "w") as f:
        f.write("{\n" + ",\n".join(lines) + "\n}\n")


if __name__ == "__main__":
    import sys
    assert sys.argv[1:] == ["--record"], __doc__
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    FusedTreeLearner._pick_chunk = _small_window
    out = {f"{b}-{w}-{c}": _serial(b, w, c, "sorted") for b, w, c in CASES}
    out["data_parallel"] = _data_parallel("sorted")
    _write_golden({name: _golden(name, got) for name, got in out.items()})
    print("recorded", GOLDEN, len(out))
