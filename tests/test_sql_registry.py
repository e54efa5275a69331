"""L5 parity: every function name registered by the reference's
resources/ddl/define-all.hive must resolve in our registry."""

import pytest

from hivemall_tpu.sql import get_function, list_functions

# Extracted verbatim from /root/reference/resources/ddl/define-all.hive
# (`create temporary function <name>`), deprecated names excluded.
DEFINE_ALL_NAMES = """
hivemall_version train_perceptron train_pa train_pa1 train_pa2 train_cw
train_arow train_arowh train_scw train_scw2 train_adagrad_rda
train_multiclass_perceptron train_multiclass_pa train_multiclass_pa1
train_multiclass_pa2 train_multiclass_cw train_multiclass_arow
train_multiclass_arowh train_multiclass_scw train_multiclass_scw2
cosine_similarity jaccard_similarity angular_similarity euclid_similarity
distance2similarity popcnt kld hamming_distance euclid_distance
cosine_distance angular_distance jaccard_distance manhattan_distance
minkowski_distance minhashes minhash bbit_minhash voted_avg weight_voted_avg
max_label maxrow argmin_kld mhash sha1 array_hash_values prefixed_hash_values
feature_hashing polynomial_features powered_features rescale zscore
l2_normalize amplify rand_amplify add_bias sort_by_feature extract_feature
extract_weight add_feature_index feature feature_index conv2dense
to_dense_features to_dense to_sparse_features to_sparse quantify
vectorize_features categorical_features ffm_features indexed_features
quantified_features quantitative_features binarize_label bpr_sampling
item_pairs_sampling populate_not_in tf logress train_logistic_regr
train_pa1_regr train_pa1a_regr train_pa2_regr train_pa2a_regr train_arow_regr
train_arowe_regr train_arowe2_regr train_adagrad_regr train_adadelta_regr
float_array array_remove sort_and_uniq_array subarray_endwith
subarray_startwith array_concat concat_array subarray array_avg array_sum
to_string_array array_intersect bits_collect to_bits unbits bits_or inflate
deflate map_get_sum map_tail_n to_map to_ordered_map sigmoid taskid jobid
rowid distcache_gets jobconf_gets generate_series convert_label x_rank
each_top_k tokenize is_stopword split_words normalize_unicode base91 unbase91
lr_datagen f1score mae mse rmse r2 ndcg logloss mf_predict train_mf_sgd
train_mf_adagrad train_bprmf bprmf_predict fm_predict train_fm train_ffm
ffm_predict train_randomforest_classifier train_randomforest_regressor
train_randomforest_regr tree_predict rf_ensemble guess_attribute_types
""".split()

MACRO_NAMES = ["java_min", "max2", "min2", "rand_gid", "rand_gid2", "idf", "tfidf"]


@pytest.mark.parametrize("name", DEFINE_ALL_NAMES)
def test_define_all_name_resolves(name):
    assert callable(get_function(name))


@pytest.mark.parametrize("name", MACRO_NAMES)
def test_macro_resolves(name):
    assert callable(get_function(name))


def test_macros_behave():
    assert get_function("max2")(1, 2) == 2
    assert get_function("min2")(1, 2) == 1
    assert get_function("idf")(1.0, 10.0) == pytest.approx(2.0)
    assert get_function("tfidf")(0.5, 1.0, 10.0) == pytest.approx(1.0)
    assert 0 <= get_function("rand_gid2")(10, 42) < 10


def test_unknown_raises():
    with pytest.raises(KeyError):
        get_function("nope")


def test_list_functions_size():
    # reference registers ~150 names (including aliases); we must be in range
    assert len(list_functions()) >= 150


def test_version_function():
    assert "tpu" in get_function("hivemall_version")()


@pytest.mark.parametrize("entry,rows", [
    ("train_arow", [["1:1.0", "2:0.5"]]),
    ("train_fm", [["1:1.0", "2:0.5"]]),
    ("train_ffm", [["1:1:1.0", "2:2:0.5"]]),
])
def test_removed_backend_flag_is_refused_as_unknown(entry, rows):
    """`-mxu_scatter` left with its backend (docs/migration.md): a call that
    still carries it is refused like any other unknown option, in words,
    before a row is staged."""
    from hivemall_tpu.utils.options import OptionError

    with pytest.raises(OptionError, match="unknown option '-mxu_scatter'"):
        get_function(entry)(rows, [1.0], "-mini_batch 4 -mxu_scatter")
