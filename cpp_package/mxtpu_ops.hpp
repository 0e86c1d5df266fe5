// GENERATED by tools/gen_cpp_wrappers.py — do not edit.
// One typed wrapper per registered op, built on the C ABI
// construction tier (the OpWrapperGenerator.py analogue).
#ifndef MXTPU_OPS_HPP_
#define MXTPU_OPS_HPP_

#include "mxtpu_cpp.hpp"

namespace mxtpu {
namespace cpp {
namespace op {

inline Symbol Activation(const std::string& symbol_name, const Symbol& data, const std::string& act_type) {
  detail::ParamMap params__;
  params__.emplace_back("act_type", act_type);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Activation", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol BatchNorm(const std::string& symbol_name, const Symbol& data, const Symbol* gamma = nullptr, const Symbol* beta = nullptr, int64_t axis = 1, bool cudnn_off = false, double eps = 0.001, bool fix_gamma = true, double momentum = 0.9, bool output_mean_var = false, bool use_global_stats = false) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  params__.emplace_back("eps", detail::str(eps));
  params__.emplace_back("fix_gamma", detail::str(fix_gamma));
  params__.emplace_back("momentum", detail::str(momentum));
  params__.emplace_back("output_mean_var", detail::str(output_mean_var));
  params__.emplace_back("use_global_stats", detail::str(use_global_stats));
  const char* keys__[] = {"data", "gamma", "beta"};
  const Symbol* ins__[] = {&data, gamma, beta};
  return detail::CreateOp("BatchNorm", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol BatchNorm_v1(const std::string& symbol_name, const Symbol& data, const Symbol* gamma = nullptr, const Symbol* beta = nullptr, int64_t axis = 1, bool cudnn_off = false, double eps = 0.001, bool fix_gamma = true, double momentum = 0.9, bool output_mean_var = false, bool use_global_stats = false) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  params__.emplace_back("eps", detail::str(eps));
  params__.emplace_back("fix_gamma", detail::str(fix_gamma));
  params__.emplace_back("momentum", detail::str(momentum));
  params__.emplace_back("output_mean_var", detail::str(output_mean_var));
  params__.emplace_back("use_global_stats", detail::str(use_global_stats));
  const char* keys__[] = {"data", "gamma", "beta"};
  const Symbol* ins__[] = {&data, gamma, beta};
  return detail::CreateOp("BatchNorm_v1", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol BilinearSampler(const std::string& symbol_name, const Symbol& data, const Symbol* grid = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "grid"};
  const Symbol* ins__[] = {&data, grid};
  return detail::CreateOp("BilinearSampler", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol BlockDiffusionNoise(const std::string& symbol_name, const Symbol& data, int64_t block, int64_t mask_id, double eps = 0.001, int64_t pad_id = 0, int64_t seed = -1) {
  detail::ParamMap params__;
  params__.emplace_back("block", detail::str(block));
  params__.emplace_back("mask_id", detail::str(mask_id));
  params__.emplace_back("eps", detail::str(eps));
  params__.emplace_back("pad_id", detail::str(pad_id));
  params__.emplace_back("seed", detail::str(seed));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("BlockDiffusionNoise", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol BlockGrad(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("BlockGrad", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol CTCLoss(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("CTCLoss", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol Cast(const std::string& symbol_name, const Symbol& data, const std::string& dtype) {
  detail::ParamMap params__;
  params__.emplace_back("dtype", dtype);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Cast", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol CausalConv1D(const std::string& symbol_name, const Symbol& data, int64_t kernel, const Symbol* weight = nullptr, const std::string& act_type = "silu", bool no_bias = true, int64_t num_group = 0) {
  detail::ParamMap params__;
  params__.emplace_back("kernel", detail::str(kernel));
  params__.emplace_back("act_type", act_type);
  params__.emplace_back("no_bias", detail::str(no_bias));
  params__.emplace_back("num_group", detail::str(num_group));
  const char* keys__[] = {"data", "weight"};
  const Symbol* ins__[] = {&data, weight};
  return detail::CreateOp("CausalConv1D", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol Concat(const std::string& symbol_name, const std::vector<const Symbol*>& data, int64_t dim = 1) {
  detail::ParamMap params__;
  params__.emplace_back("dim", detail::str(dim));
  return detail::CreateOpN("Concat", symbol_name, data, params__);
}

inline Symbol Convolution(const std::string& symbol_name, const Symbol& data, const std::string& kernel, int64_t num_filter, const Symbol* weight = nullptr, const Symbol* bias = nullptr, bool cudnn_off = false, const std::string& cudnn_tune = "", const std::string& dilate = "", const std::string& layout = "", bool no_bias = false, int64_t num_group = 1, const std::string& pad = "", const std::string& stride = "", int64_t workspace = 1024) {
  detail::ParamMap params__;
  params__.emplace_back("kernel", kernel);
  params__.emplace_back("num_filter", detail::str(num_filter));
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  if (!cudnn_tune.empty()) params__.emplace_back("cudnn_tune", cudnn_tune);
  if (!dilate.empty()) params__.emplace_back("dilate", dilate);
  if (!layout.empty()) params__.emplace_back("layout", layout);
  params__.emplace_back("no_bias", detail::str(no_bias));
  params__.emplace_back("num_group", detail::str(num_group));
  if (!pad.empty()) params__.emplace_back("pad", pad);
  if (!stride.empty()) params__.emplace_back("stride", stride);
  params__.emplace_back("workspace", detail::str(workspace));
  const char* keys__[] = {"data", "weight", "bias"};
  const Symbol* ins__[] = {&data, weight, bias};
  return detail::CreateOp("Convolution", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol Convolution_v1(const std::string& symbol_name, const Symbol& data, const std::string& kernel, int64_t num_filter, const Symbol* weight = nullptr, const Symbol* bias = nullptr, bool cudnn_off = false, const std::string& cudnn_tune = "", const std::string& dilate = "", const std::string& layout = "", bool no_bias = false, int64_t num_group = 1, const std::string& pad = "", const std::string& stride = "", int64_t workspace = 1024) {
  detail::ParamMap params__;
  params__.emplace_back("kernel", kernel);
  params__.emplace_back("num_filter", detail::str(num_filter));
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  if (!cudnn_tune.empty()) params__.emplace_back("cudnn_tune", cudnn_tune);
  if (!dilate.empty()) params__.emplace_back("dilate", dilate);
  if (!layout.empty()) params__.emplace_back("layout", layout);
  params__.emplace_back("no_bias", detail::str(no_bias));
  params__.emplace_back("num_group", detail::str(num_group));
  if (!pad.empty()) params__.emplace_back("pad", pad);
  if (!stride.empty()) params__.emplace_back("stride", stride);
  params__.emplace_back("workspace", detail::str(workspace));
  const char* keys__[] = {"data", "weight", "bias"};
  const Symbol* ins__[] = {&data, weight, bias};
  return detail::CreateOp("Convolution_v1", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol Correlation(const std::string& symbol_name, const Symbol& data1, const Symbol* data2 = nullptr, bool is_multiply = true, int64_t kernel_size = 1, int64_t max_displacement = 1, int64_t pad_size = 0, int64_t stride1 = 1, int64_t stride2 = 1) {
  detail::ParamMap params__;
  params__.emplace_back("is_multiply", detail::str(is_multiply));
  params__.emplace_back("kernel_size", detail::str(kernel_size));
  params__.emplace_back("max_displacement", detail::str(max_displacement));
  params__.emplace_back("pad_size", detail::str(pad_size));
  params__.emplace_back("stride1", detail::str(stride1));
  params__.emplace_back("stride2", detail::str(stride2));
  const char* keys__[] = {"data1", "data2"};
  const Symbol* ins__[] = {&data1, data2};
  return detail::CreateOp("Correlation", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol Crop(const std::string& symbol_name, const std::vector<const Symbol*>& data, bool center_crop = false, const std::string& h_w = "(0, 0)", const std::string& offset = "(0, 0)") {
  detail::ParamMap params__;
  params__.emplace_back("center_crop", detail::str(center_crop));
  params__.emplace_back("h_w", h_w);
  params__.emplace_back("offset", offset);
  return detail::CreateOpN("Crop", symbol_name, data, params__);
}

inline Symbol Custom(const std::string& symbol_name, const std::vector<const Symbol*>& data) {
  detail::ParamMap params__;
  return detail::CreateOpN("Custom", symbol_name, data, params__);
}

inline Symbol Deconvolution(const std::string& symbol_name, const Symbol& data, const std::string& kernel, int64_t num_filter, const Symbol* weight = nullptr, const Symbol* bias = nullptr, const std::string& adj = "", bool cudnn_off = false, const std::string& cudnn_tune = "", const std::string& dilate = "", const std::string& layout = "", bool no_bias = false, int64_t num_group = 1, const std::string& pad = "", const std::string& stride = "", const std::string& target_shape = "", int64_t workspace = 1024) {
  detail::ParamMap params__;
  params__.emplace_back("kernel", kernel);
  params__.emplace_back("num_filter", detail::str(num_filter));
  if (!adj.empty()) params__.emplace_back("adj", adj);
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  if (!cudnn_tune.empty()) params__.emplace_back("cudnn_tune", cudnn_tune);
  if (!dilate.empty()) params__.emplace_back("dilate", dilate);
  if (!layout.empty()) params__.emplace_back("layout", layout);
  params__.emplace_back("no_bias", detail::str(no_bias));
  params__.emplace_back("num_group", detail::str(num_group));
  if (!pad.empty()) params__.emplace_back("pad", pad);
  if (!stride.empty()) params__.emplace_back("stride", stride);
  if (!target_shape.empty()) params__.emplace_back("target_shape", target_shape);
  params__.emplace_back("workspace", detail::str(workspace));
  const char* keys__[] = {"data", "weight", "bias"};
  const Symbol* ins__[] = {&data, weight, bias};
  return detail::CreateOp("Deconvolution", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol Dropout(const std::string& symbol_name, const Symbol& data, const std::string& mode = "training", double p = 0.5) {
  detail::ParamMap params__;
  params__.emplace_back("mode", mode);
  params__.emplace_back("p", detail::str(p));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Dropout", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol ElementWiseSum(const std::string& symbol_name, const std::vector<const Symbol*>& data) {
  detail::ParamMap params__;
  return detail::CreateOpN("ElementWiseSum", symbol_name, data, params__);
}

inline Symbol Embedding(const std::string& symbol_name, const Symbol& data, int64_t input_dim, int64_t output_dim, const Symbol* weight = nullptr, const std::string& dtype = "float32") {
  detail::ParamMap params__;
  params__.emplace_back("input_dim", detail::str(input_dim));
  params__.emplace_back("output_dim", detail::str(output_dim));
  params__.emplace_back("dtype", dtype);
  const char* keys__[] = {"data", "weight"};
  const Symbol* ins__[] = {&data, weight};
  return detail::CreateOp("Embedding", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol ExitSoftmaxOutput(const std::string& symbol_name, const Symbol& exit1, int64_t num_exits, const Symbol* gate1 = nullptr, const Symbol* label = nullptr, double beta = 0.0, double ignore_label = -1.0, bool use_ignore = false) {
  detail::ParamMap params__;
  params__.emplace_back("num_exits", detail::str(num_exits));
  params__.emplace_back("beta", detail::str(beta));
  params__.emplace_back("ignore_label", detail::str(ignore_label));
  params__.emplace_back("use_ignore", detail::str(use_ignore));
  const char* keys__[] = {"exit1", "gate1", "label"};
  const Symbol* ins__[] = {&exit1, gate1, label};
  return detail::CreateOp("ExitSoftmaxOutput", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol Flatten(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Flatten", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol FullyConnected(const std::string& symbol_name, const Symbol& data, int64_t num_hidden, const Symbol* weight = nullptr, const Symbol* bias = nullptr, bool flatten = true, bool no_bias = false) {
  detail::ParamMap params__;
  params__.emplace_back("num_hidden", detail::str(num_hidden));
  params__.emplace_back("flatten", detail::str(flatten));
  params__.emplace_back("no_bias", detail::str(no_bias));
  const char* keys__[] = {"data", "weight", "bias"};
  const Symbol* ins__[] = {&data, weight, bias};
  return detail::CreateOp("FullyConnected", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol GatedDeltaRule(const std::string& symbol_name, const Symbol& query, const Symbol* key = nullptr, const Symbol* value = nullptr, const Symbol* g = nullptr, const Symbol* beta = nullptr, int64_t chunk = 64) {
  detail::ParamMap params__;
  params__.emplace_back("chunk", detail::str(chunk));
  const char* keys__[] = {"query", "key", "value", "g", "beta"};
  const Symbol* ins__[] = {&query, key, value, g, beta};
  return detail::CreateOp("GatedDeltaRule", symbol_name, 5, keys__, ins__, params__);
}

inline Symbol GridGenerator(const std::string& symbol_name, const Symbol& data, const std::string& target_shape = "(0, 0)", const std::string& transform_type = "affine") {
  detail::ParamMap params__;
  params__.emplace_back("target_shape", target_shape);
  params__.emplace_back("transform_type", transform_type);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("GridGenerator", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol InstanceNorm(const std::string& symbol_name, const Symbol& data, const Symbol* gamma = nullptr, const Symbol* beta = nullptr, double eps = 0.001) {
  detail::ParamMap params__;
  params__.emplace_back("eps", detail::str(eps));
  const char* keys__[] = {"data", "gamma", "beta"};
  const Symbol* ins__[] = {&data, gamma, beta};
  return detail::CreateOp("InstanceNorm", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol L2Normalization(const std::string& symbol_name, const Symbol& data, double eps = 1e-10, const std::string& mode = "instance") {
  detail::ParamMap params__;
  params__.emplace_back("eps", detail::str(eps));
  params__.emplace_back("mode", mode);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("L2Normalization", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol LRN(const std::string& symbol_name, const Symbol& data, int64_t nsize, double alpha = 0.0001, double beta = 0.75, double knorm = 2.0) {
  detail::ParamMap params__;
  params__.emplace_back("nsize", detail::str(nsize));
  params__.emplace_back("alpha", detail::str(alpha));
  params__.emplace_back("beta", detail::str(beta));
  params__.emplace_back("knorm", detail::str(knorm));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("LRN", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol LayerNorm(const std::string& symbol_name, const Symbol& data, const Symbol* gamma = nullptr, const Symbol* beta = nullptr, int64_t axis = -1, double eps = 1e-05) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("eps", detail::str(eps));
  const char* keys__[] = {"data", "gamma", "beta"};
  const Symbol* ins__[] = {&data, gamma, beta};
  return detail::CreateOp("LayerNorm", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol LeakyReLU(const std::string& symbol_name, const Symbol& data, const std::string& act_type = "leaky", double lower_bound = 0.125, double slope = 0.25, double upper_bound = 0.334) {
  detail::ParamMap params__;
  params__.emplace_back("act_type", act_type);
  params__.emplace_back("lower_bound", detail::str(lower_bound));
  params__.emplace_back("slope", detail::str(slope));
  params__.emplace_back("upper_bound", detail::str(upper_bound));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("LeakyReLU", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol LinearRegressionOutput(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double grad_scale = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("LinearRegressionOutput", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol LogisticRegressionOutput(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double grad_scale = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("LogisticRegressionOutput", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol MAERegressionOutput(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double grad_scale = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("MAERegressionOutput", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol MakeLoss(const std::string& symbol_name, const Symbol& data, double grad_scale = 1.0, const std::string& normalization = "null", double valid_thresh = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  params__.emplace_back("normalization", normalization);
  params__.emplace_back("valid_thresh", detail::str(valid_thresh));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("MakeLoss", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol MoE(const std::string& symbol_name, const Symbol& data, int64_t num_experts, int64_t num_hidden, int64_t top_k, const Symbol* router_weight = nullptr, const Symbol* gate_weight = nullptr, const Symbol* up_weight = nullptr, const Symbol* down_weight = nullptr, bool expert_bias = false, int64_t expert_offset = 0, double lb_coef = 0.0, int64_t num_local_experts = 0, bool route_norm = false, double route_scale = 1.0, const std::string& router = "weight", const std::string& score_func = "softmax", double z_coef = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("num_experts", detail::str(num_experts));
  params__.emplace_back("num_hidden", detail::str(num_hidden));
  params__.emplace_back("top_k", detail::str(top_k));
  params__.emplace_back("expert_bias", detail::str(expert_bias));
  params__.emplace_back("expert_offset", detail::str(expert_offset));
  params__.emplace_back("lb_coef", detail::str(lb_coef));
  params__.emplace_back("num_local_experts", detail::str(num_local_experts));
  params__.emplace_back("route_norm", detail::str(route_norm));
  params__.emplace_back("route_scale", detail::str(route_scale));
  params__.emplace_back("router", router);
  params__.emplace_back("score_func", score_func);
  params__.emplace_back("z_coef", detail::str(z_coef));
  const char* keys__[] = {"data", "router_weight", "gate_weight", "up_weight", "down_weight"};
  const Symbol* ins__[] = {&data, router_weight, gate_weight, up_weight, down_weight};
  return detail::CreateOp("MoE", symbol_name, 5, keys__, ins__, params__);
}

inline Symbol MultiBoxDetection(const std::string& symbol_name, const Symbol& cls_prob, const Symbol* loc_pred = nullptr, const Symbol* anchor = nullptr, int64_t background_id = 0, bool clip = true, bool force_suppress = false, double nms_threshold = 0.5, int64_t nms_topk = -1, double threshold = 0.01, const std::string& variances = "(0.1, 0.1, 0.2, 0.2)") {
  detail::ParamMap params__;
  params__.emplace_back("background_id", detail::str(background_id));
  params__.emplace_back("clip", detail::str(clip));
  params__.emplace_back("force_suppress", detail::str(force_suppress));
  params__.emplace_back("nms_threshold", detail::str(nms_threshold));
  params__.emplace_back("nms_topk", detail::str(nms_topk));
  params__.emplace_back("threshold", detail::str(threshold));
  params__.emplace_back("variances", variances);
  const char* keys__[] = {"cls_prob", "loc_pred", "anchor"};
  const Symbol* ins__[] = {&cls_prob, loc_pred, anchor};
  return detail::CreateOp("MultiBoxDetection", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol MultiBoxPrior(const std::string& symbol_name, const Symbol& data, bool clip = false, const std::string& offsets = "(0.5, 0.5)", const std::string& ratios = "(1.0)", const std::string& sizes = "(1.0)", const std::string& steps = "") {
  detail::ParamMap params__;
  params__.emplace_back("clip", detail::str(clip));
  params__.emplace_back("offsets", offsets);
  params__.emplace_back("ratios", ratios);
  params__.emplace_back("sizes", sizes);
  if (!steps.empty()) params__.emplace_back("steps", steps);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("MultiBoxPrior", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol MultiBoxTarget(const std::string& symbol_name, const Symbol& anchor, const Symbol* label = nullptr, const Symbol* cls_pred = nullptr, double ignore_label = -1.0, int64_t minimum_negative_samples = 0, double negative_mining_ratio = -1.0, double negative_mining_thresh = 0.5, double overlap_threshold = 0.5, const std::string& variances = "(0.1, 0.1, 0.2, 0.2)") {
  detail::ParamMap params__;
  params__.emplace_back("ignore_label", detail::str(ignore_label));
  params__.emplace_back("minimum_negative_samples", detail::str(minimum_negative_samples));
  params__.emplace_back("negative_mining_ratio", detail::str(negative_mining_ratio));
  params__.emplace_back("negative_mining_thresh", detail::str(negative_mining_thresh));
  params__.emplace_back("overlap_threshold", detail::str(overlap_threshold));
  params__.emplace_back("variances", variances);
  const char* keys__[] = {"anchor", "label", "cls_pred"};
  const Symbol* ins__[] = {&anchor, label, cls_pred};
  return detail::CreateOp("MultiBoxTarget", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol Pad(const std::string& symbol_name, const Symbol& data, const std::string& pad_width, double constant_value = 0.0, const std::string& mode = "constant") {
  detail::ParamMap params__;
  params__.emplace_back("pad_width", pad_width);
  params__.emplace_back("constant_value", detail::str(constant_value));
  params__.emplace_back("mode", mode);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Pad", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol Pooling(const std::string& symbol_name, const Symbol& data, bool cudnn_off = false, bool global_pool = false, const std::string& kernel = "()", const std::string& pad = "", const std::string& pool_type = "max", const std::string& pooling_convention = "valid", const std::string& stride = "") {
  detail::ParamMap params__;
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  params__.emplace_back("global_pool", detail::str(global_pool));
  params__.emplace_back("kernel", kernel);
  if (!pad.empty()) params__.emplace_back("pad", pad);
  params__.emplace_back("pool_type", pool_type);
  params__.emplace_back("pooling_convention", pooling_convention);
  if (!stride.empty()) params__.emplace_back("stride", stride);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Pooling", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol Pooling_v1(const std::string& symbol_name, const Symbol& data, bool cudnn_off = false, bool global_pool = false, const std::string& kernel = "()", const std::string& pad = "", const std::string& pool_type = "max", const std::string& pooling_convention = "valid", const std::string& stride = "") {
  detail::ParamMap params__;
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  params__.emplace_back("global_pool", detail::str(global_pool));
  params__.emplace_back("kernel", kernel);
  if (!pad.empty()) params__.emplace_back("pad", pad);
  params__.emplace_back("pool_type", pool_type);
  params__.emplace_back("pooling_convention", pooling_convention);
  if (!stride.empty()) params__.emplace_back("stride", stride);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Pooling_v1", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol Proposal(const std::string& symbol_name, const Symbol& cls_prob, const Symbol* bbox_pred = nullptr, const Symbol* im_info = nullptr, int64_t feature_stride = 16, bool iou_loss = false, bool output_score = false, const std::string& ratios = "(0.5, 1.0, 2.0)", int64_t rpn_min_size = 16, int64_t rpn_post_nms_top_n = 300, int64_t rpn_pre_nms_top_n = 6000, const std::string& scales = "(4.0, 8.0, 16.0, 32.0)", double threshold = 0.7) {
  detail::ParamMap params__;
  params__.emplace_back("feature_stride", detail::str(feature_stride));
  params__.emplace_back("iou_loss", detail::str(iou_loss));
  params__.emplace_back("output_score", detail::str(output_score));
  params__.emplace_back("ratios", ratios);
  params__.emplace_back("rpn_min_size", detail::str(rpn_min_size));
  params__.emplace_back("rpn_post_nms_top_n", detail::str(rpn_post_nms_top_n));
  params__.emplace_back("rpn_pre_nms_top_n", detail::str(rpn_pre_nms_top_n));
  params__.emplace_back("scales", scales);
  params__.emplace_back("threshold", detail::str(threshold));
  const char* keys__[] = {"cls_prob", "bbox_pred", "im_info"};
  const Symbol* ins__[] = {&cls_prob, bbox_pred, im_info};
  return detail::CreateOp("Proposal", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol RMSNorm(const std::string& symbol_name, const Symbol& data, const Symbol* gamma = nullptr, double eps = 1e-05) {
  detail::ParamMap params__;
  params__.emplace_back("eps", detail::str(eps));
  const char* keys__[] = {"data", "gamma"};
  const Symbol* ins__[] = {&data, gamma};
  return detail::CreateOp("RMSNorm", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol RNN(const std::string& symbol_name, const Symbol& data, const std::string& mode, int64_t num_layers, int64_t state_size, const Symbol* parameters = nullptr, const Symbol* state = nullptr, bool bidirectional = false, const std::string& lstm_q_ = "", double p = 0.0, const std::string& pkeep_ = "", bool state_outputs = false) {
  detail::ParamMap params__;
  params__.emplace_back("mode", mode);
  params__.emplace_back("num_layers", detail::str(num_layers));
  params__.emplace_back("state_size", detail::str(state_size));
  params__.emplace_back("bidirectional", detail::str(bidirectional));
  if (!lstm_q_.empty()) params__.emplace_back("lstm_q_", lstm_q_);
  params__.emplace_back("p", detail::str(p));
  if (!pkeep_.empty()) params__.emplace_back("pkeep_", pkeep_);
  params__.emplace_back("state_outputs", detail::str(state_outputs));
  const char* keys__[] = {"data", "parameters", "state"};
  const Symbol* ins__[] = {&data, parameters, state};
  return detail::CreateOp("RNN", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol ROIPooling(const std::string& symbol_name, const Symbol& data, const std::string& pooled_size, double spatial_scale, const Symbol* rois = nullptr) {
  detail::ParamMap params__;
  params__.emplace_back("pooled_size", pooled_size);
  params__.emplace_back("spatial_scale", detail::str(spatial_scale));
  const char* keys__[] = {"data", "rois"};
  const Symbol* ins__[] = {&data, rois};
  return detail::CreateOp("ROIPooling", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol Reshape(const std::string& symbol_name, const Symbol& data, const std::string& shape, bool keep_highest = false, bool reverse = false, const std::string& target_shape = "") {
  detail::ParamMap params__;
  params__.emplace_back("shape", shape);
  params__.emplace_back("keep_highest", detail::str(keep_highest));
  params__.emplace_back("reverse", detail::str(reverse));
  if (!target_shape.empty()) params__.emplace_back("target_shape", target_shape);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("Reshape", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol RingAttention(const std::string& symbol_name, const Symbol& query, const Symbol* key = nullptr, const Symbol* value = nullptr, const std::string& axis_name = "sp", const std::string& batch_axis = "", bool causal = false, int64_t diffusion_block = 0, double index_loss_coef = 0.0, double scale = -1.0, int64_t select_top_k = 0, int64_t window = 0) {
  detail::ParamMap params__;
  params__.emplace_back("axis_name", axis_name);
  if (!batch_axis.empty()) params__.emplace_back("batch_axis", batch_axis);
  params__.emplace_back("causal", detail::str(causal));
  params__.emplace_back("diffusion_block", detail::str(diffusion_block));
  params__.emplace_back("index_loss_coef", detail::str(index_loss_coef));
  params__.emplace_back("scale", detail::str(scale));
  params__.emplace_back("select_top_k", detail::str(select_top_k));
  params__.emplace_back("window", detail::str(window));
  const char* keys__[] = {"query", "key", "value"};
  const Symbol* ins__[] = {&query, key, value};
  return detail::CreateOp("RingAttention", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol RotaryEmbedding(const std::string& symbol_name, const Symbol& data, double attention_factor = 0.0, double base = 10000.0, double beta_fast = 32.0, double beta_slow = 1.0, double factor = 1.0, bool interleaved = false, int64_t original_max_position = 0, int64_t rotary_dim = 0, const std::string& scaling = "") {
  detail::ParamMap params__;
  params__.emplace_back("attention_factor", detail::str(attention_factor));
  params__.emplace_back("base", detail::str(base));
  params__.emplace_back("beta_fast", detail::str(beta_fast));
  params__.emplace_back("beta_slow", detail::str(beta_slow));
  params__.emplace_back("factor", detail::str(factor));
  params__.emplace_back("interleaved", detail::str(interleaved));
  params__.emplace_back("original_max_position", detail::str(original_max_position));
  params__.emplace_back("rotary_dim", detail::str(rotary_dim));
  if (!scaling.empty()) params__.emplace_back("scaling", scaling);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("RotaryEmbedding", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol SVMOutput(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double margin = 1.0, double regularization_coefficient = 1.0, bool use_linear = false) {
  detail::ParamMap params__;
  params__.emplace_back("margin", detail::str(margin));
  params__.emplace_back("regularization_coefficient", detail::str(regularization_coefficient));
  params__.emplace_back("use_linear", detail::str(use_linear));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("SVMOutput", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol SelectiveScan(const std::string& symbol_name, const Symbol& data, const Symbol* dt = nullptr, const Symbol* A_log = nullptr, const Symbol* B = nullptr, const Symbol* C = nullptr, const Symbol* D = nullptr, const Symbol* dt_bias = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "dt", "A_log", "B", "C", "D", "dt_bias"};
  const Symbol* ins__[] = {&data, dt, A_log, B, C, D, dt_bias};
  return detail::CreateOp("SelectiveScan", symbol_name, 7, keys__, ins__, params__);
}

inline Symbol SequenceLast(const std::string& symbol_name, const Symbol& data, bool use_sequence_length = false) {
  detail::ParamMap params__;
  params__.emplace_back("use_sequence_length", detail::str(use_sequence_length));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SequenceLast", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol SequenceMask(const std::string& symbol_name, const Symbol& data, bool use_sequence_length = false, double value = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("use_sequence_length", detail::str(use_sequence_length));
  params__.emplace_back("value", detail::str(value));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SequenceMask", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol SequenceReverse(const std::string& symbol_name, const Symbol& data, bool use_sequence_length = false) {
  detail::ParamMap params__;
  params__.emplace_back("use_sequence_length", detail::str(use_sequence_length));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SequenceReverse", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol SliceChannel(const std::string& symbol_name, const Symbol& data, int64_t num_outputs, int64_t axis = 1, bool squeeze_axis = false) {
  detail::ParamMap params__;
  params__.emplace_back("num_outputs", detail::str(num_outputs));
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("squeeze_axis", detail::str(squeeze_axis));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SliceChannel", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol Softmax(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double grad_scale = 1.0, double ignore_label = -1.0, bool multi_output = false, const std::string& normalization = "null", bool out_grad = false, bool preserve_shape = false, bool sample_weight = false, bool use_ignore = false) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  params__.emplace_back("ignore_label", detail::str(ignore_label));
  params__.emplace_back("multi_output", detail::str(multi_output));
  params__.emplace_back("normalization", normalization);
  params__.emplace_back("out_grad", detail::str(out_grad));
  params__.emplace_back("preserve_shape", detail::str(preserve_shape));
  params__.emplace_back("sample_weight", detail::str(sample_weight));
  params__.emplace_back("use_ignore", detail::str(use_ignore));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("Softmax", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol SoftmaxActivation(const std::string& symbol_name, const Symbol& data, const std::string& mode = "instance") {
  detail::ParamMap params__;
  params__.emplace_back("mode", mode);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SoftmaxActivation", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol SoftmaxOutput(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr, double grad_scale = 1.0, double ignore_label = -1.0, bool multi_output = false, const std::string& normalization = "null", bool out_grad = false, bool preserve_shape = false, bool sample_weight = false, bool use_ignore = false) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  params__.emplace_back("ignore_label", detail::str(ignore_label));
  params__.emplace_back("multi_output", detail::str(multi_output));
  params__.emplace_back("normalization", normalization);
  params__.emplace_back("out_grad", detail::str(out_grad));
  params__.emplace_back("preserve_shape", detail::str(preserve_shape));
  params__.emplace_back("sample_weight", detail::str(sample_weight));
  params__.emplace_back("use_ignore", detail::str(use_ignore));
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("SoftmaxOutput", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol SpatialTransformer(const std::string& symbol_name, const Symbol& data, const std::string& target_shape, const Symbol* loc = nullptr, bool cudnn_off = false, const std::string& sampler_type = "bilinear", const std::string& transform_type = "affine") {
  detail::ParamMap params__;
  params__.emplace_back("target_shape", target_shape);
  params__.emplace_back("cudnn_off", detail::str(cudnn_off));
  params__.emplace_back("sampler_type", sampler_type);
  params__.emplace_back("transform_type", transform_type);
  const char* keys__[] = {"data", "loc"};
  const Symbol* ins__[] = {&data, loc};
  return detail::CreateOp("SpatialTransformer", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol SwapAxis(const std::string& symbol_name, const Symbol& data, int64_t dim1 = 0, int64_t dim2 = 0) {
  detail::ParamMap params__;
  params__.emplace_back("dim1", detail::str(dim1));
  params__.emplace_back("dim2", detail::str(dim2));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("SwapAxis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol UpSampling(const std::string& symbol_name, const std::vector<const Symbol*>& data, int64_t scale, const std::string& multi_input_mode = "concat", int64_t num_filter = 0, const std::string& sample_type = "nearest", int64_t workspace = 512) {
  detail::ParamMap params__;
  params__.emplace_back("scale", detail::str(scale));
  params__.emplace_back("multi_input_mode", multi_input_mode);
  params__.emplace_back("num_filter", detail::str(num_filter));
  params__.emplace_back("sample_type", sample_type);
  params__.emplace_back("workspace", detail::str(workspace));
  return detail::CreateOpN("UpSampling", symbol_name, data, params__);
}

inline Symbol abs(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("abs", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol adam_update(const std::string& symbol_name, const Symbol& weight, double lr, const Symbol* grad = nullptr, const Symbol* mean = nullptr, const Symbol* var = nullptr, double beta1 = 0.9, double beta2 = 0.999, double clip_gradient = -1.0, double epsilon = 1e-08, double rescale_grad = 1.0, double wd = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("lr", detail::str(lr));
  params__.emplace_back("beta1", detail::str(beta1));
  params__.emplace_back("beta2", detail::str(beta2));
  params__.emplace_back("clip_gradient", detail::str(clip_gradient));
  params__.emplace_back("epsilon", detail::str(epsilon));
  params__.emplace_back("rescale_grad", detail::str(rescale_grad));
  params__.emplace_back("wd", detail::str(wd));
  const char* keys__[] = {"weight", "grad", "mean", "var"};
  const Symbol* ins__[] = {&weight, grad, mean, var};
  return detail::CreateOp("adam_update", symbol_name, 4, keys__, ins__, params__);
}

inline Symbol add_n(const std::string& symbol_name, const std::vector<const Symbol*>& data) {
  detail::ParamMap params__;
  return detail::CreateOpN("add_n", symbol_name, data, params__);
}

inline Symbol arccos(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arccos", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol arccosh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arccosh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol arcsin(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arcsin", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol arcsinh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arcsinh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol arctan(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arctan", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol arctanh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("arctanh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol argmax(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("argmax", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol argmax_channel(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("argmax_channel", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol argmin(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("argmin", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol argsort(const std::string& symbol_name, const Symbol& data, int64_t axis = -1, bool is_ascend = true) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("is_ascend", detail::str(is_ascend));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("argsort", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol batch_dot(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr, bool transpose_a = false, bool transpose_b = false) {
  detail::ParamMap params__;
  params__.emplace_back("transpose_a", detail::str(transpose_a));
  params__.emplace_back("transpose_b", detail::str(transpose_b));
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("batch_dot", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol batch_take(const std::string& symbol_name, const Symbol& a, const Symbol* indices = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"a", "indices"};
  const Symbol* ins__[] = {&a, indices};
  return detail::CreateOp("batch_take", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_add(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_add", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_axes(const std::string& symbol_name, const Symbol& data, const std::string& axis = "()", const std::string& size = "()") {
  detail::ParamMap params__;
  params__.emplace_back("axis", axis);
  params__.emplace_back("size", size);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("broadcast_axes", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol broadcast_axis(const std::string& symbol_name, const Symbol& data, const std::string& axis = "()", const std::string& size = "()") {
  detail::ParamMap params__;
  params__.emplace_back("axis", axis);
  params__.emplace_back("size", size);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("broadcast_axis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol broadcast_div(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_div", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_equal(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_equal", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_greater(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_greater", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_greater_equal(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_greater_equal", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_hypot(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_hypot", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_lesser(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_lesser", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_lesser_equal(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_lesser_equal", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_maximum(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_maximum", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_minimum(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_minimum", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_minus(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_minus", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_mod(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_mod", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_mul(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_mul", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_not_equal(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_not_equal", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_plus(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_plus", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_power(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_power", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_sub(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("broadcast_sub", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol broadcast_to(const std::string& symbol_name, const Symbol& data, const std::string& shape) {
  detail::ParamMap params__;
  params__.emplace_back("shape", shape);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("broadcast_to", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol cast(const std::string& symbol_name, const Symbol& data, const std::string& dtype) {
  detail::ParamMap params__;
  params__.emplace_back("dtype", dtype);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("cast", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol cbrt(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("cbrt", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol ceil(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("ceil", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol clip(const std::string& symbol_name, const Symbol& data, double a_max, double a_min) {
  detail::ParamMap params__;
  params__.emplace_back("a_max", detail::str(a_max));
  params__.emplace_back("a_min", detail::str(a_min));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("clip", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol concat(const std::string& symbol_name, const std::vector<const Symbol*>& data, int64_t dim = 1) {
  detail::ParamMap params__;
  params__.emplace_back("dim", detail::str(dim));
  return detail::CreateOpN("concat", symbol_name, data, params__);
}

inline Symbol cos(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("cos", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol cosh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("cosh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol count_sketch(const std::string& symbol_name, const Symbol& data, int64_t out_dim, const Symbol* h = nullptr, const Symbol* s = nullptr, int64_t processing_batch_size = 32) {
  detail::ParamMap params__;
  params__.emplace_back("out_dim", detail::str(out_dim));
  params__.emplace_back("processing_batch_size", detail::str(processing_batch_size));
  const char* keys__[] = {"data", "h", "s"};
  const Symbol* ins__[] = {&data, h, s};
  return detail::CreateOp("count_sketch", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol crop(const std::string& symbol_name, const Symbol& data, const std::string& begin, const std::string& end) {
  detail::ParamMap params__;
  params__.emplace_back("begin", begin);
  params__.emplace_back("end", end);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("crop", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol ctc_loss(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("ctc_loss", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol degrees(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("degrees", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol dequantize(const std::string& symbol_name, const Symbol& data, const Symbol* min_range = nullptr, const Symbol* max_range = nullptr, const std::string& out_type = "float32") {
  detail::ParamMap params__;
  params__.emplace_back("out_type", out_type);
  const char* keys__[] = {"data", "min_range", "max_range"};
  const Symbol* ins__[] = {&data, min_range, max_range};
  return detail::CreateOp("dequantize", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol dot(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr, bool transpose_a = false, bool transpose_b = false) {
  detail::ParamMap params__;
  params__.emplace_back("transpose_a", detail::str(transpose_a));
  params__.emplace_back("transpose_b", detail::str(transpose_b));
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("dot", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol elemwise_add(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("elemwise_add", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol elemwise_div(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("elemwise_div", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol elemwise_mul(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("elemwise_mul", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol elemwise_sub(const std::string& symbol_name, const Symbol& lhs, const Symbol* rhs = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"lhs", "rhs"};
  const Symbol* ins__[] = {&lhs, rhs};
  return detail::CreateOp("elemwise_sub", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol erf(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("erf", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol exp(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("exp", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol expand_dims(const std::string& symbol_name, const Symbol& data, int64_t axis) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("expand_dims", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol expm1(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("expm1", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol fft(const std::string& symbol_name, const Symbol& data, int64_t compute_size = 128) {
  detail::ParamMap params__;
  params__.emplace_back("compute_size", detail::str(compute_size));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("fft", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol fix(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("fix", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol flatten(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("flatten", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol flip(const std::string& symbol_name, const Symbol& data, const std::string& axis) {
  detail::ParamMap params__;
  params__.emplace_back("axis", axis);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("flip", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol floor(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("floor", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol gamma(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("gamma", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol gammaln(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("gammaln", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol gather_nd(const std::string& symbol_name, const Symbol& data, const Symbol* indices = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "indices"};
  const Symbol* ins__[] = {&data, indices};
  return detail::CreateOp("gather_nd", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol identity(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("identity", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol ifft(const std::string& symbol_name, const Symbol& data, int64_t compute_size = 128) {
  detail::ParamMap params__;
  params__.emplace_back("compute_size", detail::str(compute_size));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("ifft", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol log(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("log", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol log10(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("log10", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol log1p(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("log1p", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol log2(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("log2", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol log_softmax(const std::string& symbol_name, const Symbol& data, int64_t axis = -1, double temperature = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("temperature", detail::str(temperature));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("log_softmax", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol logical_not(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("logical_not", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol make_loss(const std::string& symbol_name, const Symbol& data, double grad_scale = 1.0, const std::string& normalization = "null", double valid_thresh = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("grad_scale", detail::str(grad_scale));
  params__.emplace_back("normalization", normalization);
  params__.emplace_back("valid_thresh", detail::str(valid_thresh));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("make_loss", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol max(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("max", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol max_axis(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("max_axis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol mean(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("mean", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol min(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("min", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol min_axis(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("min_axis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol multibox_detection(const std::string& symbol_name, const Symbol& cls_prob, const Symbol* loc_pred = nullptr, const Symbol* anchor = nullptr, int64_t background_id = 0, bool clip = true, bool force_suppress = false, double nms_threshold = 0.5, int64_t nms_topk = -1, double threshold = 0.01, const std::string& variances = "(0.1, 0.1, 0.2, 0.2)") {
  detail::ParamMap params__;
  params__.emplace_back("background_id", detail::str(background_id));
  params__.emplace_back("clip", detail::str(clip));
  params__.emplace_back("force_suppress", detail::str(force_suppress));
  params__.emplace_back("nms_threshold", detail::str(nms_threshold));
  params__.emplace_back("nms_topk", detail::str(nms_topk));
  params__.emplace_back("threshold", detail::str(threshold));
  params__.emplace_back("variances", variances);
  const char* keys__[] = {"cls_prob", "loc_pred", "anchor"};
  const Symbol* ins__[] = {&cls_prob, loc_pred, anchor};
  return detail::CreateOp("multibox_detection", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol multibox_prior(const std::string& symbol_name, const Symbol& data, bool clip = false, const std::string& offsets = "(0.5, 0.5)", const std::string& ratios = "(1.0)", const std::string& sizes = "(1.0)", const std::string& steps = "") {
  detail::ParamMap params__;
  params__.emplace_back("clip", detail::str(clip));
  params__.emplace_back("offsets", offsets);
  params__.emplace_back("ratios", ratios);
  params__.emplace_back("sizes", sizes);
  if (!steps.empty()) params__.emplace_back("steps", steps);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("multibox_prior", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol multibox_target(const std::string& symbol_name, const Symbol& anchor, const Symbol* label = nullptr, const Symbol* cls_pred = nullptr, double ignore_label = -1.0, int64_t minimum_negative_samples = 0, double negative_mining_ratio = -1.0, double negative_mining_thresh = 0.5, double overlap_threshold = 0.5, const std::string& variances = "(0.1, 0.1, 0.2, 0.2)") {
  detail::ParamMap params__;
  params__.emplace_back("ignore_label", detail::str(ignore_label));
  params__.emplace_back("minimum_negative_samples", detail::str(minimum_negative_samples));
  params__.emplace_back("negative_mining_ratio", detail::str(negative_mining_ratio));
  params__.emplace_back("negative_mining_thresh", detail::str(negative_mining_thresh));
  params__.emplace_back("overlap_threshold", detail::str(overlap_threshold));
  params__.emplace_back("variances", variances);
  const char* keys__[] = {"anchor", "label", "cls_pred"};
  const Symbol* ins__[] = {&anchor, label, cls_pred};
  return detail::CreateOp("multibox_target", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol nanprod(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("nanprod", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol nansum(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("nansum", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol negative(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("negative", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol norm(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("norm", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol normal(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double loc = 0.0, double scale = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("loc", detail::str(loc));
  params__.emplace_back("scale", detail::str(scale));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("normal", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol one_hot(const std::string& symbol_name, const Symbol& indices, int64_t depth, const std::string& dtype = "float32", double off_value = 0.0, double on_value = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("depth", detail::str(depth));
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("off_value", detail::str(off_value));
  params__.emplace_back("on_value", detail::str(on_value));
  const char* keys__[] = {"indices"};
  const Symbol* ins__[] = {&indices};
  return detail::CreateOp("one_hot", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol ones_like(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("ones_like", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol pad(const std::string& symbol_name, const Symbol& data, const std::string& pad_width, double constant_value = 0.0, const std::string& mode = "constant") {
  detail::ParamMap params__;
  params__.emplace_back("pad_width", pad_width);
  params__.emplace_back("constant_value", detail::str(constant_value));
  params__.emplace_back("mode", mode);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("pad", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol pick(const std::string& symbol_name, const Symbol& data, const Symbol* index = nullptr, int64_t axis = -1, bool keepdims = false) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data", "index"};
  const Symbol* ins__[] = {&data, index};
  return detail::CreateOp("pick", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol prod(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("prod", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol proposal(const std::string& symbol_name, const Symbol& cls_prob, const Symbol* bbox_pred = nullptr, const Symbol* im_info = nullptr, int64_t feature_stride = 16, bool iou_loss = false, bool output_score = false, const std::string& ratios = "(0.5, 1.0, 2.0)", int64_t rpn_min_size = 16, int64_t rpn_post_nms_top_n = 300, int64_t rpn_pre_nms_top_n = 6000, const std::string& scales = "(4.0, 8.0, 16.0, 32.0)", double threshold = 0.7) {
  detail::ParamMap params__;
  params__.emplace_back("feature_stride", detail::str(feature_stride));
  params__.emplace_back("iou_loss", detail::str(iou_loss));
  params__.emplace_back("output_score", detail::str(output_score));
  params__.emplace_back("ratios", ratios);
  params__.emplace_back("rpn_min_size", detail::str(rpn_min_size));
  params__.emplace_back("rpn_post_nms_top_n", detail::str(rpn_post_nms_top_n));
  params__.emplace_back("rpn_pre_nms_top_n", detail::str(rpn_pre_nms_top_n));
  params__.emplace_back("scales", scales);
  params__.emplace_back("threshold", detail::str(threshold));
  const char* keys__[] = {"cls_prob", "bbox_pred", "im_info"};
  const Symbol* ins__[] = {&cls_prob, bbox_pred, im_info};
  return detail::CreateOp("proposal", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol quantize(const std::string& symbol_name, const Symbol& data, const Symbol* min_range = nullptr, const Symbol* max_range = nullptr, const std::string& out_type = "int8") {
  detail::ParamMap params__;
  params__.emplace_back("out_type", out_type);
  const char* keys__[] = {"data", "min_range", "max_range"};
  const Symbol* ins__[] = {&data, min_range, max_range};
  return detail::CreateOp("quantize", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol radians(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("radians", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol random_exponential(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double lam = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("lam", detail::str(lam));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_exponential", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_gamma(const std::string& symbol_name, double alpha = 1.0, double beta = 1.0, const std::string& ctx = "", const std::string& dtype = "float32", const std::string& shape = "()") {
  detail::ParamMap params__;
  params__.emplace_back("alpha", detail::str(alpha));
  params__.emplace_back("beta", detail::str(beta));
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_gamma", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_generalized_negative_binomial(const std::string& symbol_name, double alpha = 1.0, const std::string& ctx = "", const std::string& dtype = "float32", double mu = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  params__.emplace_back("alpha", detail::str(alpha));
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("mu", detail::str(mu));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_generalized_negative_binomial", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_negative_binomial(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", int64_t k = 1, double p = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("k", detail::str(k));
  params__.emplace_back("p", detail::str(p));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_negative_binomial", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_normal(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double loc = 0.0, double scale = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("loc", detail::str(loc));
  params__.emplace_back("scale", detail::str(scale));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_normal", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_poisson(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double lam = 1.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("lam", detail::str(lam));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_poisson", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol random_uniform(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double high = 1.0, double low = 0.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("high", detail::str(high));
  params__.emplace_back("low", detail::str(low));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("random_uniform", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol rcbrt(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("rcbrt", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol reciprocal(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("reciprocal", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol relu(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("relu", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol repeat(const std::string& symbol_name, const Symbol& data, int64_t repeats, const std::string& axis = "") {
  detail::ParamMap params__;
  params__.emplace_back("repeats", detail::str(repeats));
  if (!axis.empty()) params__.emplace_back("axis", axis);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("repeat", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol reshape(const std::string& symbol_name, const Symbol& data, const std::string& shape, bool keep_highest = false, bool reverse = false, const std::string& target_shape = "") {
  detail::ParamMap params__;
  params__.emplace_back("shape", shape);
  params__.emplace_back("keep_highest", detail::str(keep_highest));
  params__.emplace_back("reverse", detail::str(reverse));
  if (!target_shape.empty()) params__.emplace_back("target_shape", target_shape);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("reshape", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol reverse(const std::string& symbol_name, const Symbol& data, const std::string& axis) {
  detail::ParamMap params__;
  params__.emplace_back("axis", axis);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("reverse", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol rint(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("rint", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol rmsprop_update(const std::string& symbol_name, const Symbol& weight, double lr, const Symbol* grad = nullptr, const Symbol* n = nullptr, double clip_gradient = -1.0, double clip_weights = -1.0, double epsilon = 1e-08, double gamma1 = 0.95, double rescale_grad = 1.0, double wd = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("lr", detail::str(lr));
  params__.emplace_back("clip_gradient", detail::str(clip_gradient));
  params__.emplace_back("clip_weights", detail::str(clip_weights));
  params__.emplace_back("epsilon", detail::str(epsilon));
  params__.emplace_back("gamma1", detail::str(gamma1));
  params__.emplace_back("rescale_grad", detail::str(rescale_grad));
  params__.emplace_back("wd", detail::str(wd));
  const char* keys__[] = {"weight", "grad", "n"};
  const Symbol* ins__[] = {&weight, grad, n};
  return detail::CreateOp("rmsprop_update", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol rmspropalex_update(const std::string& symbol_name, const Symbol& weight, double lr, const Symbol* grad = nullptr, const Symbol* n = nullptr, const Symbol* g = nullptr, const Symbol* delta = nullptr, double clip_gradient = -1.0, double clip_weights = -1.0, double epsilon = 1e-08, double gamma1 = 0.95, double gamma2 = 0.9, double rescale_grad = 1.0, double wd = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("lr", detail::str(lr));
  params__.emplace_back("clip_gradient", detail::str(clip_gradient));
  params__.emplace_back("clip_weights", detail::str(clip_weights));
  params__.emplace_back("epsilon", detail::str(epsilon));
  params__.emplace_back("gamma1", detail::str(gamma1));
  params__.emplace_back("gamma2", detail::str(gamma2));
  params__.emplace_back("rescale_grad", detail::str(rescale_grad));
  params__.emplace_back("wd", detail::str(wd));
  const char* keys__[] = {"weight", "grad", "n", "g", "delta"};
  const Symbol* ins__[] = {&weight, grad, n, g, delta};
  return detail::CreateOp("rmspropalex_update", symbol_name, 5, keys__, ins__, params__);
}

inline Symbol rnn(const std::string& symbol_name, const Symbol& data, const std::string& mode, int64_t num_layers, int64_t state_size, const Symbol* parameters = nullptr, const Symbol* state = nullptr, bool bidirectional = false, const std::string& lstm_q_ = "", double p = 0.0, const std::string& pkeep_ = "", bool state_outputs = false) {
  detail::ParamMap params__;
  params__.emplace_back("mode", mode);
  params__.emplace_back("num_layers", detail::str(num_layers));
  params__.emplace_back("state_size", detail::str(state_size));
  params__.emplace_back("bidirectional", detail::str(bidirectional));
  if (!lstm_q_.empty()) params__.emplace_back("lstm_q_", lstm_q_);
  params__.emplace_back("p", detail::str(p));
  if (!pkeep_.empty()) params__.emplace_back("pkeep_", pkeep_);
  params__.emplace_back("state_outputs", detail::str(state_outputs));
  const char* keys__[] = {"data", "parameters", "state"};
  const Symbol* ins__[] = {&data, parameters, state};
  return detail::CreateOp("rnn", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol round(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("round", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol rsqrt(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("rsqrt", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sample_multinomial(const std::string& symbol_name, const Symbol& data, const std::string& dtype = "int32", const std::string& get_prob = "False", const std::string& shape = "()") {
  detail::ParamMap params__;
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("get_prob", get_prob);
  params__.emplace_back("shape", shape);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sample_multinomial", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sgd_mom_update(const std::string& symbol_name, const Symbol& weight, double lr, const Symbol* grad = nullptr, const Symbol* mom = nullptr, double clip_gradient = -1.0, double momentum = 0.0, double rescale_grad = 1.0, double wd = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("lr", detail::str(lr));
  params__.emplace_back("clip_gradient", detail::str(clip_gradient));
  params__.emplace_back("momentum", detail::str(momentum));
  params__.emplace_back("rescale_grad", detail::str(rescale_grad));
  params__.emplace_back("wd", detail::str(wd));
  const char* keys__[] = {"weight", "grad", "mom"};
  const Symbol* ins__[] = {&weight, grad, mom};
  return detail::CreateOp("sgd_mom_update", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol sgd_update(const std::string& symbol_name, const Symbol& weight, double lr, const Symbol* grad = nullptr, double clip_gradient = -1.0, double rescale_grad = 1.0, double wd = 0.0) {
  detail::ParamMap params__;
  params__.emplace_back("lr", detail::str(lr));
  params__.emplace_back("clip_gradient", detail::str(clip_gradient));
  params__.emplace_back("rescale_grad", detail::str(rescale_grad));
  params__.emplace_back("wd", detail::str(wd));
  const char* keys__[] = {"weight", "grad"};
  const Symbol* ins__[] = {&weight, grad};
  return detail::CreateOp("sgd_update", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol sigmoid(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sigmoid", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sign(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sign", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sin(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sin", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sinh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sinh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol slice(const std::string& symbol_name, const Symbol& data, const std::string& begin, const std::string& end) {
  detail::ParamMap params__;
  params__.emplace_back("begin", begin);
  params__.emplace_back("end", end);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("slice", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol slice_axis(const std::string& symbol_name, const Symbol& data, int64_t axis, int64_t begin = 0, const std::string& end = "") {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("begin", detail::str(begin));
  if (!end.empty()) params__.emplace_back("end", end);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("slice_axis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol smooth_l1(const std::string& symbol_name, const Symbol& data, double scalar = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("scalar", detail::str(scalar));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("smooth_l1", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol softmax(const std::string& symbol_name, const Symbol& data, int64_t axis = -1, double temperature = 1.0) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("temperature", detail::str(temperature));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("softmax", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol softmax_cross_entropy(const std::string& symbol_name, const Symbol& data, const Symbol* label = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"data", "label"};
  const Symbol* ins__[] = {&data, label};
  return detail::CreateOp("softmax_cross_entropy", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol softsign(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("softsign", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sort(const std::string& symbol_name, const Symbol& data, int64_t axis = -1, bool is_ascend = true) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("is_ascend", detail::str(is_ascend));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sort", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol split(const std::string& symbol_name, const Symbol& data, int64_t num_outputs, int64_t axis = 1, bool squeeze_axis = false) {
  detail::ParamMap params__;
  params__.emplace_back("num_outputs", detail::str(num_outputs));
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("squeeze_axis", detail::str(squeeze_axis));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("split", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sqrt(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sqrt", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol square(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("square", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol stack(const std::string& symbol_name, const std::vector<const Symbol*>& data, int64_t axis = 0) {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  return detail::CreateOpN("stack", symbol_name, data, params__);
}

inline Symbol stop_gradient(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("stop_gradient", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sum(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sum", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol sum_axis(const std::string& symbol_name, const Symbol& data, const std::string& axis = "", bool exclude = false, bool keepdims = false) {
  detail::ParamMap params__;
  if (!axis.empty()) params__.emplace_back("axis", axis);
  params__.emplace_back("exclude", detail::str(exclude));
  params__.emplace_back("keepdims", detail::str(keepdims));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("sum_axis", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol swapaxes(const std::string& symbol_name, const Symbol& data, int64_t dim1 = 0, int64_t dim2 = 0) {
  detail::ParamMap params__;
  params__.emplace_back("dim1", detail::str(dim1));
  params__.emplace_back("dim2", detail::str(dim2));
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("swapaxes", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol take(const std::string& symbol_name, const Symbol& a, const Symbol* indices = nullptr, int64_t axis = 0, const std::string& mode = "clip") {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("mode", mode);
  const char* keys__[] = {"a", "indices"};
  const Symbol* ins__[] = {&a, indices};
  return detail::CreateOp("take", symbol_name, 2, keys__, ins__, params__);
}

inline Symbol tan(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("tan", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol tanh(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("tanh", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol tile(const std::string& symbol_name, const Symbol& data, const std::string& reps) {
  detail::ParamMap params__;
  params__.emplace_back("reps", reps);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("tile", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol topk(const std::string& symbol_name, const Symbol& data, int64_t axis = -1, bool is_ascend = false, int64_t k = 1, const std::string& ret_typ = "indices") {
  detail::ParamMap params__;
  params__.emplace_back("axis", detail::str(axis));
  params__.emplace_back("is_ascend", detail::str(is_ascend));
  params__.emplace_back("k", detail::str(k));
  params__.emplace_back("ret_typ", ret_typ);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("topk", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol transpose(const std::string& symbol_name, const Symbol& data, const std::string& axes = "()") {
  detail::ParamMap params__;
  params__.emplace_back("axes", axes);
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("transpose", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol trunc(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("trunc", symbol_name, 1, keys__, ins__, params__);
}

inline Symbol uniform(const std::string& symbol_name, const std::string& ctx = "", const std::string& dtype = "float32", double high = 1.0, double low = 0.0, const std::string& shape = "()") {
  detail::ParamMap params__;
  if (!ctx.empty()) params__.emplace_back("ctx", ctx);
  params__.emplace_back("dtype", dtype);
  params__.emplace_back("high", detail::str(high));
  params__.emplace_back("low", detail::str(low));
  params__.emplace_back("shape", shape);
  return detail::CreateOp("uniform", symbol_name, 0, nullptr, nullptr, params__);
}

inline Symbol where(const std::string& symbol_name, const Symbol& condition, const Symbol* x = nullptr, const Symbol* y = nullptr) {
  detail::ParamMap params__;
  const char* keys__[] = {"condition", "x", "y"};
  const Symbol* ins__[] = {&condition, x, y};
  return detail::CreateOp("where", symbol_name, 3, keys__, ins__, params__);
}

inline Symbol zeros_like(const std::string& symbol_name, const Symbol& data) {
  detail::ParamMap params__;
  const char* keys__[] = {"data"};
  const Symbol* ins__[] = {&data};
  return detail::CreateOp("zeros_like", symbol_name, 1, keys__, ins__, params__);
}

}  // namespace op
}  // namespace cpp
}  // namespace mxtpu

#endif  // MXTPU_OPS_HPP_
