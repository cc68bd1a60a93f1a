# Generated by mmlspark_tpu.codegen.generate_r_package — do not edit.

#' Per-row next-token log-probabilities of a token-id column.
#' (model: mmlspark_tpu.models.causal_lm.CausalLMScorer)
#' @export
mt_causal_lm_scorer <- function(buckets = NULL, config = NULL, input_col = NULL, output_col = NULL, variables = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.causal_lm")
  do.call(m$CausalLMScorer, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.image_featurizer.ImageFeaturizer)
#' @export
mt_image_featurizer <- function(apply_fn = NULL, batch_size = 64L, bgr_input = FALSE, cut_output_layers = 1L, drop_na = TRUE, image_size = NULL, input_col = NULL, model_name = "ResNet8_Digits", output_col = NULL, repo_dir = NULL, variables = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.image_featurizer")
  do.call(m$ImageFeaturizer, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.gbdt.estimators.LightGBMClassificationModel)
#' @export
mt_light_gbm_classification_model <- function(features_col = "features", model_string = "", prediction_col = "prediction", probability_col = "probability", raw_prediction_col = "raw_prediction") {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMClassificationModel, args)
}

#' Base class for all stages.
#' (estimator: mmlspark_tpu.models.gbdt.estimators.LightGBMClassifier)
#' @export
mt_light_gbm_classifier <- function(alpha = 0.9, bagging_fraction = 1.0, bagging_freq = 0L, boost_from_average = TRUE, boosting_type = "gbdt", categorical_slot_indexes = NULL, checkpoint_dir = "", checkpoint_every = 10L, default_listen_port = 12400L, delegate = NULL, drop_rate = 0.1, early_stopping_round = 0L, eval_at = 5L, fair_c = 1.0, feature_fraction = 1.0, features_col = "features", fused_rounds = 0L, growth_policy = "lossguide", init_score_col = NULL, label_col = "label", lambda_l1 = 0.0, lambda_l2 = 0.0, learning_rate = 0.1, max_bin = 255L, max_depth = -1L, max_drop = 50L, metric = "", min_data_in_leaf = 20L, min_gain_to_split = 0.0, min_sum_hessian_in_leaf = 0.001, model_string = "", num_batches = 0L, num_iterations = 100L, num_leaves = 31L, objective = "binary", other_rate = 0.1, parallelism = "data_parallel", poisson_max_delta_step = 0.7, prediction_col = "prediction", probability_col = "probability", raw_prediction_col = "raw_prediction", resume_from = "", seed = 0L, skip_drop = 0.5, top_k = 20L, top_rate = 0.2, tweedie_variance_power = 1.5, use_barrier_execution_mode = FALSE, validation_indicator_col = NULL, verbosity = -1L, weight_col = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMClassifier, args)
}

#' Base class for all stages.
#' (estimator: mmlspark_tpu.models.gbdt.estimators.LightGBMRanker)
#' @export
mt_light_gbm_ranker <- function(alpha = 0.9, bagging_fraction = 1.0, bagging_freq = 0L, boost_from_average = TRUE, boosting_type = "gbdt", categorical_slot_indexes = NULL, checkpoint_dir = "", checkpoint_every = 10L, default_listen_port = 12400L, delegate = NULL, drop_rate = 0.1, early_stopping_round = 0L, eval_at = 5L, evaluate_at = list(1L, 3L, 5L, 10L), fair_c = 1.0, feature_fraction = 1.0, features_col = "features", fused_rounds = 0L, group_col = NULL, growth_policy = "lossguide", init_score_col = NULL, label_col = "label", lambda_l1 = 0.0, lambda_l2 = 0.0, learning_rate = 0.1, max_bin = 255L, max_depth = -1L, max_drop = 50L, metric = "", min_data_in_leaf = 20L, min_gain_to_split = 0.0, min_sum_hessian_in_leaf = 0.001, model_string = "", num_batches = 0L, num_iterations = 100L, num_leaves = 31L, objective = "lambdarank", other_rate = 0.1, parallelism = "data_parallel", poisson_max_delta_step = 0.7, prediction_col = "prediction", resume_from = "", seed = 0L, skip_drop = 0.5, top_k = 20L, top_rate = 0.2, tweedie_variance_power = 1.5, use_barrier_execution_mode = FALSE, validation_indicator_col = NULL, verbosity = -1L, weight_col = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMRanker, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.gbdt.estimators.LightGBMRankerModel)
#' @export
mt_light_gbm_ranker_model <- function(features_col = "features", model_string = "", prediction_col = "prediction") {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMRankerModel, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.gbdt.estimators.LightGBMRegressionModel)
#' @export
mt_light_gbm_regression_model <- function(features_col = "features", model_string = "", prediction_col = "prediction") {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMRegressionModel, args)
}

#' Base class for all stages.
#' (estimator: mmlspark_tpu.models.gbdt.estimators.LightGBMRegressor)
#' @export
mt_light_gbm_regressor <- function(alpha = 0.9, bagging_fraction = 1.0, bagging_freq = 0L, boost_from_average = TRUE, boosting_type = "gbdt", categorical_slot_indexes = NULL, checkpoint_dir = "", checkpoint_every = 10L, default_listen_port = 12400L, delegate = NULL, drop_rate = 0.1, early_stopping_round = 0L, eval_at = 5L, fair_c = 1.0, feature_fraction = 1.0, features_col = "features", fused_rounds = 0L, growth_policy = "lossguide", init_score_col = NULL, label_col = "label", lambda_l1 = 0.0, lambda_l2 = 0.0, learning_rate = 0.1, max_bin = 255L, max_depth = -1L, max_drop = 50L, metric = "", min_data_in_leaf = 20L, min_gain_to_split = 0.0, min_sum_hessian_in_leaf = 0.001, model_string = "", num_batches = 0L, num_iterations = 100L, num_leaves = 31L, objective = "regression", other_rate = 0.1, parallelism = "data_parallel", poisson_max_delta_step = 0.7, prediction_col = "prediction", resume_from = "", seed = 0L, skip_drop = 0.5, top_k = 20L, top_rate = 0.2, tweedie_variance_power = 1.5, use_barrier_execution_mode = FALSE, validation_indicator_col = NULL, verbosity = -1L, weight_col = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.gbdt.estimators")
  do.call(m$LightGBMRegressor, args)
}

#' Ridge regression by normal equations on device (one MXU solve).
#' (estimator: mmlspark_tpu.models.linear.LinearRegression)
#' @export
mt_linear_regression <- function(features_col = "features", label_col = "label", reg_param = 1e-06, weight_col = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.linear")
  do.call(m$LinearRegression, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.linear.LinearRegressionModel)
#' @export
mt_linear_regression_model <- function(bias = 0.0, features_col = "features", prediction_col = "prediction", weights = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.linear")
  do.call(m$LinearRegressionModel, args)
}

#' Base class for all stages.
#' (estimator: mmlspark_tpu.models.linear.LogisticRegression)
#' @export
mt_logistic_regression <- function(features_col = "features", label_col = "label", learning_rate = 0.5, max_iter = 200L, reg_param = 0.0001, weight_col = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.linear")
  do.call(m$LogisticRegression, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.linear.LogisticRegressionModel)
#' @export
mt_logistic_regression_model <- function(bias = NULL, features_col = "features", num_classes = 2L, prediction_col = "prediction", probability_col = "probability", raw_prediction_col = "raw_prediction", weights = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.linear")
  do.call(m$LogisticRegressionModel, args)
}

#' A fitted transformer.
#' (model: mmlspark_tpu.models.xla_model.XLAModel)
#' @export
mt_xla_model <- function(apply_fn = NULL, batch_size = 64L, input_col = NULL, input_dtype = "float32", output_col = NULL, output_node = NULL, variables = NULL) {
  # snapshot formals BEFORE any local assignment leaks in
  args <- as.list(environment())
  args <- args[!vapply(args, is.null, logical(1))]
  m <- reticulate::import("mmlspark_tpu.models.xla_model")
  do.call(m$XLAModel, args)
}
