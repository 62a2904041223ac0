"""Plain reference for serving: LightGBM model text -> probabilities.

Its own reader of the text format and a float64 traversal of the RAW
thresholds (`value <= threshold` goes left, a negative child is leaf
`~child`), so it shares nothing with lightgbm_tpu.serving, which bins
the rows against the thresholds first, or with learner/predict.py.
Numerical splits without missing values only, which is what
benchmark/generators/forest.py writes; anything else is refused.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def parse_model_text(text: str) -> Dict[str, object]:
    head, trees = {}, []
    block = None
    for raw in text.split("\n"):
        line = raw.strip()
        if line.startswith("end of trees"):
            break
        if line.startswith("Tree="):
            block = {}
            trees.append(block)
            continue
        if "=" not in line:
            continue
        key, value = line.split("=", 1)
        (head if block is None else block)[key] = value
    if not head.get("objective", "").startswith("binary"):
        raise ValueError("the reference scores binary models, not %r"
                         % head.get("objective"))
    out = []
    for b in trees:
        if int(b.get("num_cat", 0)) or int(b.get("is_linear", 0)):
            raise ValueError("categorical or linear trees are not in the "
                             "reference")
        def arr(key, dtype):
            return np.asarray(b[key].split(" "), dtype=dtype)
        dt = arr("decision_type", np.int64)
        if np.any(dt & 1) or np.any((dt >> 2) & 3):
            raise ValueError("only numerical splits with missing_type "
                             "None are in the reference")
        out.append({"split_feature": arr("split_feature", np.int64),
                    "threshold": arr("threshold", np.float64),
                    "left": arr("left_child", np.int64),
                    "right": arr("right_child", np.int64),
                    "leaf_value": arr("leaf_value", np.float64)})
    return {"trees": out, "features": int(head["max_feature_idx"]) + 1}


def raw_scores(model: Dict[str, object], X: np.ndarray) -> np.ndarray:
    X = np.asarray(X, np.float64)
    rows = np.arange(len(X))
    total = np.zeros(len(X), np.float64)
    for tree in model["trees"]:
        node = np.zeros(len(X), np.int64)
        live = node >= 0
        while live.any():
            idx = node[live]
            go_left = X[rows[live], tree["split_feature"][idx]] <= \
                tree["threshold"][idx]
            node[live] = np.where(go_left, tree["left"][idx],
                                  tree["right"][idx])
            live = node >= 0
        total += tree["leaf_value"][~node]
    return total


def predict_proba(model: Dict[str, object], X: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-raw_scores(model, X)))
