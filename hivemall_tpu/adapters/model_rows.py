"""Typed per-family model-row iteration shared by the host-engine adapters.

Every trainer family dumps its model as relational rows at close() in the
reference (linear: BinaryOnlineClassifierUDTF.java:249-298, multiclass
per-label, FM: forwardAsIntFeature FactorizationMachineUDTF.java:446-519,
forest: RandomForestClassifierUDTF.java:343-351, GBT per round:
GradientTreeBoostingClassifierUDTF.java:525-546). The TSV bridge
(hive_transform) and the Spark adapter share this family dispatch,
yielding typed python values (lists stay lists — each adapter picks its
own array encoding: json for TSV cells, array<float> columns for Spark).
The SQL engine binding (sqlite.py) keeps its own materialization: its
tables are engine-facing (typed SQL columns, blob side tables, indexes),
not a row-stream rendering.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple


def iter_model_rows(model) -> Tuple[List[str], Iterable[tuple]]:
    """(column_names, iterable of typed row tuples) for any trained model.

    Column layouts per family (value types in parens):
    - GBT: iter(int), cls(int), model_type(str), pred_model(str),
      intercept(float), shrinkage(float), var_importance(list[float]),
      oob_error_rate(float|None), classes(str: JSON vocabulary)
    - FM: feature(int), Wi(float), Vif(list[float]|None) — w0 rides the
      feature == -1 row (the TSV/SQL convention; the reference parks it on
      feature 0's bias slot)
    - FFM: feature(int), Wi(float|None), blob(str|None) — w0 on feature -1,
      the complete compressed model (base91 text) on feature -2
    - forest: model_id(int), model_type(str), pred_model(str),
      var_importance(list[float]), oob_errors(int), oob_tests(int)
    - multiclass: label(any), feature(int), weight(float)[, covar(float)]
    - linear: feature(int), weight(float)[, covar(float)]
    """
    from ..models.ffm import TrainedFFMModel
    from ..models.fm import TrainedFMModel
    from ..models.trees.forest import TrainedForest, TrainedGBT

    if isinstance(model, TrainedGBT):
        cols = ["iter", "cls", "model_type", "pred_model", "intercept",
                "shrinkage", "var_importance", "oob_error_rate", "classes"]

        def gbt_rows():
            for m, c, mt, text, ic, sh, imp, oob, vocab in model.model_rows():
                yield (int(m), int(c), str(mt), text, float(ic), float(sh),
                       [float(x) for x in imp], oob, vocab)

        return cols, gbt_rows()

    if isinstance(model, TrainedFMModel):
        cols = ["feature", "Wi", "Vif"]

        def fm_rows():
            w0, feats, w, v = model.model_rows()
            yield (-1, float(w0), None)
            for f, wi, vi in zip(feats, w, v):
                yield (int(f), float(wi), [float(x) for x in vi])

        return cols, fm_rows()

    if isinstance(model, TrainedFFMModel):
        cols = ["feature", "Wi", "blob"]

        def ffm_rows():
            from ..tools import base91

            # the linear rows as rows; the V entries ride the blob row
            emitted = model.model_rows()
            w0, feats, w = emitted[:3]
            yield (-1, float(w0), None)
            for f, wi in zip(feats, w):
                yield (int(f), float(wi), None)
            yield (-2, None, base91(model.to_blob(rows=emitted)))

        return cols, ffm_rows()

    if isinstance(model, TrainedForest):
        cols = ["model_id", "model_type", "pred_model", "var_importance",
                "oob_errors", "oob_tests"]

        def forest_rows():
            for mid, mtype, text, imp, oe, ot in model.model_rows():
                yield (int(mid), str(mtype), text,
                       [float(x) for x in imp], int(oe), int(ot))

        return cols, forest_rows()

    if hasattr(model, "label_vocab"):  # multiclass family
        rows = model.model_rows()
        cols = (["label", "feature", "weight", "covar"] if len(rows) == 4
                else ["label", "feature", "weight"])

        def mc_rows():
            for tup in zip(*rows):
                lab, feat, w = tup[0], int(tup[1]), float(tup[2])
                if len(tup) == 4:
                    yield (lab, feat, w, float(tup[3]))
                else:
                    yield (lab, feat, w)

        return cols, mc_rows()

    if hasattr(model, "state") and hasattr(model.state, "weights"):
        from ..core.state import model_rows as linear_rows

        rows = linear_rows(model.state)
        use_cov = len(rows) == 3 and rows[2] is not None
        cols = (["feature", "weight", "covar"] if use_cov
                else ["feature", "weight"])

        def lin_rows():
            if use_cov:
                for f, w, c in zip(*rows):
                    yield (int(f), float(w), float(c))
            else:
                for f, w in zip(rows[0], rows[1]):
                    yield (int(f), float(w))

        return cols, lin_rows()

    raise ValueError(f"{type(model).__name__}: model has no row emission")
