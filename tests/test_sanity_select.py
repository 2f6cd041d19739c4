"""The fitted column filters write their kept columns once, row-major
(ISSUE 33): ``_select_columns`` and both models' ``transform_columns``
against the expression they replaced, which is kept HERE
(``X[:, keep].astype(np.float32)``).  Values are copied, never recomputed,
so every comparison is ``np.array_equal``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.ops.vector_metadata import (VectorColumnMetadata,
                                                   VectorMetadata)
from transmogrifai_tpu.preparators.sanity_checker import (
    MinVarianceFilterModel, SanityCheckerModel, _select_columns)
from transmogrifai_tpu.types.columns import FeatureColumn
from transmogrifai_tpu.types.feature_types import OPVector

D = 12


def _f32(rows):
    return (np.random.default_rng(rows).standard_normal((rows, D))
            .astype(np.float32))


def _nonfinite(rows):
    X = _f32(rows)
    X[::3, 1] = np.nan
    X[1::4, 2] = np.inf
    X[2::5, 6] = -np.inf
    X[:, 7] = np.nan
    return X


#: what reaches the filter as ``features_col.values``, by name
INPUTS = {
    "f32": _f32,
    "f64": lambda rows: _f32(rows).astype(np.float64) / 3.0,
    "int32": lambda rows: (_f32(rows) * 1000).astype(np.int32),
    "bool": lambda rows: _f32(rows) > 0,
    "fortran": lambda rows: np.asfortranarray(_f32(rows)),
    "col_strided": lambda rows: np.random.default_rng(1).standard_normal(
        (rows, 2 * D)).astype(np.float32)[:, ::2],
    "row_strided": lambda rows: np.random.default_rng(2).standard_normal(
        (2 * rows, D)).astype(np.float32)[::2],
    "jax": lambda rows: jnp.asarray(_f32(rows)),
    "nonfinite": _nonfinite,
}

KEEPS = {
    "none": [],
    "one": [7],
    "every_second": list(range(0, D, 2)),
    "random_sorted": sorted(np.random.default_rng(4).choice(
        D, 7, replace=False).tolist()),
    "reversed": list(range(D - 1, -1, -3)),
    "all": list(range(D)),
}

CASES = ([(kind, 50, keep) for kind in INPUTS for keep in KEEPS]
         + [("f32", rows, keep) for rows in (0, 1, 40_000) for keep in KEEPS]
         + [("nonfinite", 40_000, "every_second"), ("f64", 1, "one"),
            ("fortran", 40_000, "random_sorted"), ("jax", 0, "all")])


def _vmeta():
    return VectorMetadata("features", [
        VectorColumnMetadata(f"p{j // 2}", "Real",
                             indicator_value="NullIndicatorValue"
                             if j % 2 else None, index=j)
        for j in range(D)])


@pytest.mark.parametrize("kind,rows,keep_name", CASES,
                         ids=[f"{k}-{r}-{n}" for k, r, n in CASES])
def test_kept_columns_are_written_once_row_major(kind, rows, keep_name):
    values = INPUTS[kind](rows)
    keep = KEEPS[keep_name]
    X = np.asarray(values)
    before = X.copy()
    want = X[:, keep].astype(np.float32)         # the expression replaced
    vmeta = _vmeta()
    col = FeatureColumn(OPVector, values, vmeta=vmeta)
    checker = SanityCheckerModel(keep_indices=keep)
    variance = MinVarianceFilterModel(keep_indices=keep)
    outs = {
        "helper": _select_columns(values, np.asarray(keep, np.intp)),
        "checker": checker.transform_columns(None, col),
        "variance": variance.transform_columns(col),
        "variance2": variance.transform_columns(None, col),
    }
    handed_on = (keep_name == "all" and X.dtype == np.float32
                 and X.flags.c_contiguous)
    for name, out in outs.items():
        if name != "helper":
            assert out.ftype is OPVector and out.mask is None
            assert out.vmeta.to_json() == vmeta.select(keep).to_json(), name
            assert ([(c.parent_feature, c.indicator_value)
                     for c in out.vmeta.columns]
                    == [(f"p{j // 2}", "NullIndicatorValue" if j % 2 else None)
                        for j in keep]), name
            out = out.values
        assert type(out) is np.ndarray and out.dtype == np.float32, name
        assert out.shape == (rows, len(keep)), name
        assert out.flags.c_contiguous, name
        assert np.array_equal(out, want, equal_nan=True), name
        # a copy, but for the one case in which nothing is left to do
        assert np.shares_memory(out, X) == (handed_on and rows > 0), name
    assert np.array_equal(np.asarray(values), before, equal_nan=True)
    # the checker keeps the metadata it selected; both keep their list
    assert checker._new_vmeta is outs["checker"].vmeta
    assert variance._new_vmeta is None
    assert checker.keep_indices == keep and variance.keep_indices == keep


def test_index_array_is_built_once_and_follows_the_parameter():
    model = SanityCheckerModel(keep_indices=(3, 1))
    first = model._keep
    assert first.dtype == np.intp and first.tolist() == [3, 1]
    col = FeatureColumn(OPVector, _f32(5))
    model.transform_columns(None, col)
    model.transform_columns(None, col)
    assert model._keep is first
    assert model.get_params() == {"keep_indices": [3, 1]}
    assert model.copy().keep_indices == [3, 1]
    model.set_params(keep_indices=[0, 2, 4])
    assert model._keep.tolist() == [0, 2, 4]
    assert model.transform_columns(None, col).values.shape == (5, 3)


def test_a_column_the_vector_lacks_still_raises():
    col = FeatureColumn(OPVector, _f32(5))
    for model in (SanityCheckerModel(keep_indices=[0, D]),
                  MinVarianceFilterModel(keep_indices=[0, D])):
        with pytest.raises(IndexError):
            model.transform_columns(None, col)
