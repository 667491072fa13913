"""The plain reference: leaf-wise histogram GBDT semantics in float64 numpy.

Imports nothing of the program and takes nothing it made except its ANSWERS:
the model text (the trees the timed ``Booster.update()`` calls grew) and the
training scores the window's last iteration left on the device. It follows
EVERY boosting iteration of the run, teacher-forced the way a served model's
check is: the program's tree k fixes which rows sit in which leaf (by the raw
float32 features against the tree's real-valued thresholds -- no bin table
of the program is read), and the reference recomputes from the seed's data
alone what that tree had to say there:

- ``leaf_rows``  leaves whose row count differs (binning/partition; exact)
- ``leaf_value`` worst leaf: |value - ref| over max(|ref|, the tree's median
                 |ref|), ref = -lr * G / (H + l2) from the reference's own
                 float64 gradients at the running scores (objective,
                 histogram sums, the score update of the tree before)
- ``leaf_hess``  the same for the leaf's hessian sum
- ``leaf_value_median`` / ``leaf_hess_median``  the median leaf's gap in the
                 worst of the trees: steady from seed to seed where the
                 worst leaf swings (one small leaf's sums are what is left
                 of float32 parent-minus-sibling subtractions), so THIS is
                 what the lower-precision control has to fail; the worst
                 leaf is held to a wider limit, against planted faults
- ``split_gap``  at the root and its two children of every tree, on a
                 seeded row sample: how far the gain of the program's split
                 lies below the best split the reference finds on its own
                 threshold grid (split scan), past what the sampling alone
                 explains (``_split_nodes``); ``split_gap_tree`` and
                 ``split_gap_node`` (0 root, 1 / 2 its children) say where
                 the worst reading sits, ``trees_followed`` how many trees
                 it is the worst of, ``split_gap_plain`` what the share
                 reads with nothing credited and ``split_noise_units`` the
                 largest distance under the best, in units of the sample's
                 noise, of which SPLIT_NOISE are credited (printed, not
                 judged)
- ``train_score`` widest gap, over every training row, between the scores
                 the program holds after its last iteration and the running
                 scores, over their standard deviation (the state the
                 window leaves: the last tree's score update has no later
                 tree to show in). ``train_score_last_step`` is what it
                 would read had the last step left the state unchanged
                 (printed, not judged)
- ``trees_missing`` iterations run minus trees in the model text

The running scores are float64 sums of the PROGRAM's leaf values at the
reference's leaf of each row (each value already held to the reference), so
a fault in tree k is not charged to tree k+1 as well.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

K_EPS = 1e-15


# -- the model text ------------------------------------------------------
def parse_model(text: str) -> List[dict]:
    """Trees of a LightGBM-format model text, as arrays."""
    trees = []
    for block in text.split("\nTree=")[1:]:
        kv = {}
        for line in block.splitlines()[1:]:
            if not line.strip():
                break
            k, _, v = line.partition("=")
            kv[k] = v
        n_leaves = int(kv["num_leaves"])

        def arr(key, dtype, n):
            if n == 0 or key not in kv:
                return np.zeros(0, dtype)
            return np.array(kv[key].split(), dtype=np.float64).astype(dtype)
        trees.append({
            "num_leaves": n_leaves,
            "shrinkage": float(kv.get("shrinkage", 1.0)),
            "split_feature": arr("split_feature", np.int64, n_leaves - 1),
            "threshold": arr("threshold", np.float64, n_leaves - 1),
            "left_child": arr("left_child", np.int64, n_leaves - 1),
            "right_child": arr("right_child", np.int64, n_leaves - 1),
            "leaf_value": arr("leaf_value", np.float64, n_leaves),
            "leaf_weight": arr("leaf_weight", np.float64, n_leaves),
            "leaf_count": arr("leaf_count", np.int64, n_leaves),
        })
    return trees


def leaf_index(tree: dict, X: np.ndarray, block: int = 1 << 16) -> np.ndarray:
    """Leaf of every row: ``x <= threshold`` goes left, a negative child
    ``c`` is leaf ``~c``. The data holds no missing values. Rows go through
    in blocks that stay in the host's cache (10.5M x 28 rows, 255 leaves, on
    the chip's host: 2.0 s a tree at 2^16 rows, 3.0 s at 2^20; my chip run,
    PR 31)."""
    n = X.shape[0]
    out = np.zeros(n, np.int64)
    if tree["num_leaves"] <= 1:
        return out
    sf, thr = tree["split_feature"], tree["threshold"]
    left, right = tree["left_child"], tree["right_child"]
    for lo in range(0, n, block):
        xb = X[lo:lo + block]
        node = np.zeros(len(xb), np.int64)
        active = np.arange(len(xb))
        while active.size:
            nd = node[active]
            go_left = xb[active, sf[nd]].astype(np.float64) <= thr[nd]
            nxt = np.where(go_left, left[nd], right[nd])
            node[active] = nxt
            active = active[nxt >= 0]
        out[lo:lo + block] = ~node
    return out


def tree_scores(trees: List[dict], X: np.ndarray) -> np.ndarray:
    s = np.zeros(X.shape[0], np.float64)
    for t in trees:
        s += t["leaf_value"][leaf_index(t, X)]
    return s


# -- objectives ----------------------------------------------------------
def binary_init(y: np.ndarray) -> float:
    p = min(max(float(np.mean(y == 1)), K_EPS), 1.0 - K_EPS)
    return float(np.log(p / (1.0 - p)))


def binary_grad(scores, y, group, obj):
    """Logistic loss, sigmoid 1: g = p - y, h = p (1 - p)."""
    p = 1.0 / (1.0 + np.exp(-scores))
    return p - y, p * (1.0 - p)


def _max_dcg(labels_sorted_desc, k, gain):
    top = labels_sorted_desc[:, :k]
    disc = 1.0 / np.log2(2.0 + np.arange(top.shape[1]))
    return np.sum(gain[top] * disc, axis=1)


def lambdarank_grad(scores, y, group, obj, budget: int = 300_000):
    """LambdaRank with the NDCG target (Burges 2010 as LightGBM states it):
    documents ranked by score (stable), pairs (i, j) with i inside the
    truncation level, i < j and different labels; per pair
    delta = |gain gap| * |discount gap| / maxDCG@k, over (0.01 + |score
    gap|) when ``norm`` and the query's scores are not all equal;
    rho = 1 / (1 + exp(sigma * (s_high - s_low))); the higher-labelled
    document gets -sigma * delta * rho, the lower the opposite, both get
    the hessian sigma^2 * delta * rho (1 - rho); with ``norm`` a query's
    lambdas and hessians are scaled by log2(1 + S) / S, S the sum of
    |lambda| over its pairs, twice.

    Queries go through in chunks of similar length whose [queries,
    truncation, length] pair tensor holds ``budget`` entries: small enough
    to stay in the host's cache (3.3x faster at MSLR's size than 6M-entry
    chunks, my CPU run, PR 25)."""
    tl = int(obj["truncation_level"])
    sigma = float(obj["sigmoid"])
    norm = bool(obj["norm"])
    y_int = y.astype(np.int64)
    gain = (2.0 ** np.arange(int(y_int.max()) + 2)) - 1.0
    sizes = np.asarray(group, np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    grad = np.zeros(len(scores), np.float64)
    hess = np.zeros(len(scores), np.float64)
    order_q = np.argsort(sizes, kind="stable")
    at = 0
    while at < len(order_q):
        # a chunk of queries of similar length, padded to the longest
        L = int(sizes[order_q[at]])
        end = at + 1
        while end < len(order_q) and \
                (end - at + 1) * int(sizes[order_q[end]]) \
                * min(tl, int(sizes[order_q[end]])) <= budget:
            L = int(sizes[order_q[end]])
            end += 1
        qs = order_q[at:end]
        at = end
        nq = len(qs)
        col = np.arange(L)
        valid = col[None, :] < sizes[qs][:, None]
        idx = np.where(valid, starts[qs][:, None] + col[None, :], 0)
        S = np.where(valid, scores[idx], -np.inf)
        Y = np.where(valid, y_int[idx], 0)
        order = np.argsort(-S, axis=1, kind="stable")
        S = np.take_along_axis(S, order, 1)
        Y = np.take_along_axis(Y, order, 1)
        V = np.take_along_axis(valid, order, 1)
        idx_sorted = np.take_along_axis(idx, order, 1)
        nv = sizes[qs]
        best = S[:, 0]
        worst = S[np.arange(nq), nv - 1]
        imd = _max_dcg(-np.sort(-np.where(valid, y_int[idx], 0), axis=1),
                       tl, gain)
        imd = np.where(imd > 0, 1.0 / np.where(imd > 0, imd, 1.0), 0.0)
        T = min(tl, L)
        disc = 1.0 / np.log2(2.0 + col)
        Sf = np.where(V, S, 0.0)
        si, sj = Sf[:, :T, None], Sf[:, None, :]
        li, lj = Y[:, :T, None], Y[:, None, :]
        ok = (V[:, :T, None] & V[:, None, :]
              & (col[None, :T, None] < col[None, None, :]) & (li != lj))
        hi_is_i = li > lj
        ds = np.where(hi_is_i, si - sj, sj - si)
        delta = (np.abs(gain[li] - gain[lj])
                 * np.abs(disc[None, :T, None] - disc[None, None, :])
                 * imd[:, None, None])
        if norm:
            delta = np.where((best != worst)[:, None, None],
                             delta / (0.01 + np.abs(ds)), delta)
        rho = 1.0 / (1.0 + np.exp(sigma * ds))
        lam = np.where(ok, -sigma * delta * rho, 0.0)
        hes = np.where(ok, sigma * sigma * delta * rho * (1.0 - rho), 0.0)
        to_i = np.where(hi_is_i, lam, -lam)
        lam_s = -to_i.sum(axis=1)
        lam_s[:, :T] += to_i.sum(axis=2)
        hes_s = hes.sum(axis=1)
        hes_s[:, :T] += hes.sum(axis=2)
        if norm:
            total = -2.0 * lam.sum(axis=(1, 2))
            factor = np.where(total > 0, np.log2(1.0 + total)
                              / np.maximum(total, K_EPS), 1.0)
            lam_s *= factor[:, None]
            hes_s *= factor[:, None]
        grad[idx_sorted[V]] = lam_s[V]
        hess[idx_sorted[V]] = hes_s[V]
    return grad, hess


OBJECTIVES = {
    "binary": (binary_init, binary_grad),
    "lambdarank": (lambda y: 0.0, lambdarank_grad),
}


# -- the comparison ------------------------------------------------------
def _gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per entry: |got - ref| over max(|ref|, the median |ref|)."""
    scale = np.maximum(np.abs(ref), max(float(np.median(np.abs(ref))), K_EPS))
    return np.abs(got - ref) / scale


# The program chose its split on ALL rows; the reference judges it on a
# sample. On the sample a split's gain is (signal + noise)^2, the noise the
# sample's own: the ROOT of the program's gain lies under the root of the
# sample's best gain by some noise units whatever the gain's size, so as a
# SHARE of the best gain a sound split reads 0.01 where the gain stands far
# over the noise and 0.6 to 1 once boosting has shrunk it to the noise
# (PERF.md section 2, PR 31). A noise unit is the root of what chance alone
# gives the best of the candidates searched on the rows judged. Two numbers,
# the same for every cell, set from the per-tree readings there:
# - the program's split is credited with SPLIT_NOISE units before the share
#   is taken: at nodes whose best gain stands four times over chance or more
#   no sound split lay more than 0.92 units under the best, a random
#   threshold 3.2 and more;
# - the share is taken of at least SPLIT_FLOOR times chance: under that a
#   node counts for less the nearer its best gain comes to what chance gives
#   (a sound split's distance is then anything up to the root of the best).
SPLIT_NOISE = 2.0
SPLIT_FLOOR = 32.0


def _best_gain(cols, rows, g, h, l2, min_hess=0.0, min_rows=0.0, grid=512):
    """Best split of one node (the sample's ``rows``) over every feature,
    thresholds on a uniform grid of ``grid`` cells per feature, each child
    holding more than ``min_hess`` of hessian and at least ``min_rows``
    rows (the program's own minima, scaled down to the rows judged: the
    reference searches what the program may take, and stays the freer);
    returns the gain and how many candidates were searched. ``cols`` is the
    sample feature-major, [features, rows]."""
    G, H = g.sum(), h.sum()
    parent = G * G / (H + l2)
    best, searched = 0.0, 0
    for col in cols:
        x = col[rows].astype(np.float64)
        lo, hi = float(x.min()), float(x.max())
        if hi <= lo:
            continue
        b = np.minimum(((x - lo) * (grid / (hi - lo))).astype(np.int64),
                       grid - 1)
        gl = np.cumsum(np.bincount(b, weights=g, minlength=grid))[:-1]
        hl = np.cumsum(np.bincount(b, weights=h, minlength=grid))[:-1]
        gr, hr = G - gl, H - hl
        ok = (hl > min_hess) & (hr > min_hess)
        if min_rows > 0:
            nl = np.cumsum(np.bincount(b, minlength=grid))[:-1]
            ok &= (nl >= min_rows) & (rows.size - nl >= min_rows)
        if not ok.any():
            continue
        searched += int(ok.sum())
        gain = np.where(ok, gl * gl / np.where(ok, hl + l2, 1.0)
                        + gr * gr / np.where(ok, hr + l2, 1.0) - parent, 0.0)
        best = max(best, float(gain.max()))
    return best, searched


def _split_nodes(tree, cols, g, h, l2, share=0.0, min_hess=0.0, min_rows=0.0):
    """Root and its two children, on the sample rows that reach the node;
    yields (node, gap, plain, units) with node 0 root, 1 left, 2 right.
    ``gap`` is (best - credited) / max(best, SPLIT_FLOOR x chance), never
    below 0. ``credited`` is the gain of the program's split with its root
    raised by SPLIT_NOISE noise units, at most ``best``. ``chance``, the
    square of a noise unit, is what chance gives the best of the searched
    candidates on these rows: sum g^2 / sum h (one candidate's gain under
    no signal, in the node's own units) times 2 ln(candidates), less the
    ``share`` of all rows that the sample holds (a sample that is the whole
    set has no sampling noise and is judged by the plain share). ``plain``
    is that share, (best - the program's) / best, and ``units`` how many
    noise units the root of the program's gain lies under the best's."""
    if tree["num_leaves"] <= 1:
        return
    nodes = [(0, 0, np.arange(cols.shape[1]))]
    for depth in range(2):
        nxt = []
        for slot, nd, rows in nodes:
            if nd < 0 or rows.size < 64:
                continue
            f, thr = tree["split_feature"][nd], tree["threshold"][nd]
            gm, hm = g[rows], h[rows]
            left = cols[f, rows].astype(np.float64) <= thr
            gl, hl = gm[left].sum(), hm[left].sum()
            G, H = gm.sum(), hm.sum()
            got = (gl * gl / (hl + l2 + K_EPS)
                   + (G - gl) ** 2 / (H - hl + l2 + K_EPS) - G * G / (H + l2))
            best, searched = _best_gain(cols, rows, gm, hm, l2, min_hess,
                                        min_rows)
            if best > 0:
                chance = ((gm * gm).sum() / (H + K_EPS) * (1.0 - share)
                          * 2.0 * np.log(max(searched, 2)))
                noise, root = np.sqrt(chance), np.sqrt(max(got, 0.0))
                credited = min(best, (root + SPLIT_NOISE * noise) ** 2)
                yield (slot,
                       float((best - credited)
                             / max(best, SPLIT_FLOOR * chance)),
                       float(max(best - got, 0.0) / best),
                       float((np.sqrt(best) - root) / noise)
                       if noise > 0 else 0.0)
            nxt.append((1, tree["left_child"][nd], rows[left]))
            nxt.append((2, tree["right_child"][nd], rows[~left]))
        nodes = nxt if depth == 0 else []


def check(model_text: str, data: dict, params: dict,
          train_scores: np.ndarray, iterations_run: int, seed: int,
          sample_rows: int = 1 << 19) -> Dict[str, float]:
    """The numbers that decide ``correct`` (module docstring), from the
    program's answers and the seed's data."""
    X, y, group = data["X"], data["y"].astype(np.float64), data["group"]
    objective = params["objective"]
    init_fn, grad_fn = OBJECTIVES[objective]
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    trees = parse_model(model_text)
    out = {"trees_missing": float(max(iterations_run - len(trees), 0)),
           "leaf_rows": 0.0, "leaf_value": 0.0, "leaf_hess": 0.0,
           "leaf_value_median": 0.0, "leaf_hess_median": 0.0,
           "split_gap": 0.0, "split_gap_tree": 0.0, "split_gap_node": 0.0,
           "split_gap_plain": 0.0, "split_noise_units": 0.0,
           "trees_followed": float(len(trees))}
    rng = np.random.Generator(np.random.PCG64(seed))
    sample = np.sort(rng.choice(X.shape[0], min(sample_rows, X.shape[0]),
                                replace=False))
    cols = np.ascontiguousarray(X[sample].T)
    share = len(sample) / X.shape[0]
    # the program's minima on a child are of ALL rows: scaled to the sample,
    # and halved, so that sampling does not make the reference the stricter
    min_hess = 0.5 * share * float(params.get("min_sum_hessian_in_leaf", 0.0))
    min_rows = 0.5 * share * float(params.get("min_data_in_leaf", 0.0))
    init = init_fn(y)
    scores = np.full(X.shape[0], init, np.float64)
    step = np.zeros(0)
    for k, tree in enumerate(trees):
        g, h = grad_fn(scores, y, group, params.get("objective_params", {}))
        leaf = leaf_index(tree, X)
        nl = tree["num_leaves"]
        cnt = np.bincount(leaf, minlength=nl)
        G = np.bincount(leaf, weights=g, minlength=nl)
        H = np.bincount(leaf, weights=h, minlength=nl)
        # tree 0 carries the init score: compare the part the tree adds
        shift = init if k == 0 else 0.0
        ref_val = -lr * G / (H + l2 + K_EPS)
        if nl > 1:
            out["leaf_rows"] += float(np.sum(cnt != tree["leaf_count"]))
            for name, gaps in (
                    ("leaf_value", _gaps(tree["leaf_value"] - shift, ref_val)),
                    ("leaf_hess", _gaps(tree["leaf_weight"], H))):
                if float(gaps.max()) > out[name]:
                    out[name] = float(gaps.max())
                    # the look at a worst leaf that reads high: how small
                    # it is (rows, and its share of the tree's hessian)
                    worst = int(np.argmax(gaps))
                    out[name + "_worst_rows"] = float(cnt[worst])
                    out[name + "_worst_hess_share"] = float(H[worst] / H.sum())
                out[name + "_median"] = max(out[name + "_median"],
                                            float(np.median(gaps)))
            for node, gap, plain, units in _split_nodes(
                    tree, cols, g[sample], h[sample], l2, share, min_hess,
                    min_rows):
                out["split_gap_plain"] = max(out["split_gap_plain"], plain)
                out["split_noise_units"] = max(out["split_noise_units"],
                                               units)
                if gap > out["split_gap"]:
                    out.update(split_gap=gap, split_gap_tree=float(k),
                               split_gap_node=float(node))
        else:
            # a stump where the reference expects a tree is a lost step
            out["trees_missing"] += 1.0
        step = tree["leaf_value"][leaf] - shift
        scores += step
    sd = max(float(np.std(scores)), K_EPS)
    got = np.asarray(train_scores, np.float64).reshape(-1)
    out["train_score"] = float(np.max(np.abs(got - scores)) / sd) \
        if got.shape == scores.shape else float("inf")
    out["train_score_last_step"] = float(np.max(np.abs(step)) / sd) \
        if step.size else 0.0
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the lines to print: every number beside its limit.
    A number without a limit is printed and not judged; a limit without a
    number fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        passed = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(passed)
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(passed)})
    rows += [{"name": name, "value": value, "limit": None, "ok": True}
             for name, value in numbers.items() if name not in limits]
    return ok, rows
