// Generated by tools/gen_cpp_ops.py — DO NOT EDIT BY HAND.
//
// Typed C++ functions for every operator in the registry (parity:
// cpp-package/include/mxnet-cpp/op.h, which the reference generates from its
// nnvm registry and includes from MxNetCpp.h:37). Each function builds one
// symbolic op via Symbol::Create over the libmxtpu_train.so C ABI.
//
// Conventions:
//  - first parameter is always the symbol name ("" lets the runtime pick);
//  - Symbol parameters are the op's array inputs; optional trailing inputs
//    default to Symbol() and are dropped from the call when unset;
//  - attributes are typed from the registry defaults (int64_t/double/bool/
//    std::string/std::vector<...>); attributes whose Python default is None
//    take a RAW JSON string (e.g. "3", "[1,2]", "\"float32\"", "null");
//  - every function takes a trailing extra_attrs_json escape hatch, a JSON
//    object merged over the typed attributes.
#ifndef MXNET_TPU_CPP_OP_H_
#define MXNET_TPU_CPP_OP_H_

#include <cstdio>
#include <string>
#include <vector>

#include "train.hpp"

namespace mxnet_tpu_cpp {
namespace op {
namespace detail {

inline std::string JsonEscape(const std::string& s) {
  std::string out;
  out.reserve(s.size() + 2);
  for (char c : s) {
    switch (c) {
      case '"': out += "\\\""; break;
      case '\\': out += "\\\\"; break;
      case '\n': out += "\\n"; break;
      case '\t': out += "\\t"; break;
      default: out += c;
    }
  }
  return out;
}

inline void Key(std::string* j, const char* k) {
  *j += j->empty() ? "\"" : ", \"";
  *j += k;
  *j += "\": ";
}

inline void Attr(std::string* j, const char* k, bool v) {
  Key(j, k);
  *j += v ? "true" : "false";
}
inline void Attr(std::string* j, const char* k, int64_t v) {
  Key(j, k);
  *j += std::to_string(v);
}
inline void Attr(std::string* j, const char* k, double v) {
  char buf[32];
  std::snprintf(buf, sizeof(buf), "%.17g", v);
  Key(j, k);
  *j += buf;
}
inline void Attr(std::string* j, const char* k, const std::string& v) {
  Key(j, k);
  *j += "\"" + JsonEscape(v) + "\"";
}
// raw JSON passthrough (attributes with a None default)
struct Raw { std::string v; };
inline void Attr(std::string* j, const char* k, const Raw& v) {
  Key(j, k);
  *j += v.v;
}
inline void Attr(std::string* j, const char* k,
                 const std::vector<int64_t>& v) {
  Key(j, k);
  *j += "[";
  for (size_t i = 0; i < v.size(); ++i) {
    if (i) *j += ", ";
    *j += std::to_string(v[i]);
  }
  *j += "]";
}
inline void Attr(std::string* j, const char* k,
                 const std::vector<double>& v) {
  Key(j, k);
  *j += "[";
  char buf[32];
  for (size_t i = 0; i < v.size(); ++i) {
    if (i) *j += ", ";
    std::snprintf(buf, sizeof(buf), "%.17g", v[i]);
    *j += buf;
  }
  *j += "]";
}

inline std::string Finish(std::string j, const std::string& extra) {
  if (!extra.empty() && extra != "{}") {
    // merge the escape-hatch object: later keys win at the parser
    std::string inner = extra;
    size_t a = inner.find('{'), b = inner.rfind('}');
    if (a != std::string::npos && b != std::string::npos && b > a + 1) {
      std::string body = inner.substr(a + 1, b - a - 1);
      if (!body.empty()) {
        if (!j.empty()) j += ", ";
        j += body;
      }
    }
  }
  return "{" + j + "}";
}

inline std::vector<Symbol> Inputs(std::vector<Symbol> ins) {
  while (!ins.empty() && ins.back().handle() == nullptr) ins.pop_back();
  return ins;
}

}  // namespace detail


// Activation: positional inputs (x)
inline Symbol Activation(
    const std::string& name,
    Symbol x,
    const std::string& act_type = "relu",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "act_type", act_type);
  std::vector<Symbol> ins{x};
  return Symbol::Create("Activation", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// AdaptiveAvgPooling2D: positional inputs (data)
inline Symbol AdaptiveAvgPooling2D(
    const std::string& name,
    Symbol data,
    int64_t output_size = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "output_size", static_cast<int64_t>(output_size));
  std::vector<Symbol> ins{data};
  return Symbol::Create("AdaptiveAvgPooling2D", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BatchNorm: positional inputs (x, gamma, beta, moving_mean, moving_var)
inline Symbol BatchNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 1e-05,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int64_t axis = 1,
    bool cudnn_off = false,
    bool training = false,
    const std::string& axis_name = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "fix_gamma", fix_gamma);
  detail::Attr(&j, "use_global_stats", use_global_stats);
  detail::Attr(&j, "output_mean_var", output_mean_var);
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "cudnn_off", cudnn_off);
  detail::Attr(&j, "training", training);
  detail::Attr(&j, "axis_name", detail::Raw{axis_name});
  std::vector<Symbol> ins{x, gamma, beta, moving_mean, moving_var};
  return Symbol::Create("BatchNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BatchNormWithReLU: positional inputs (x, gamma, beta, moving_mean, moving_var)
inline Symbol BatchNormWithReLU(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, gamma, beta, moving_mean, moving_var};
  return Symbol::Create("BatchNormWithReLU", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BatchNorm_v1: positional inputs (x, gamma, beta, moving_mean, moving_var)
inline Symbol BatchNorm_v1(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, gamma, beta, moving_mean, moving_var};
  return Symbol::Create("BatchNorm_v1", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BilinearResize2D: positional inputs (x)
inline Symbol BilinearResize2D(
    const std::string& name,
    Symbol x,
    int64_t height = 0,
    int64_t width = 0,
    const std::string& scale_height = "null" /* raw JSON */,
    const std::string& scale_width = "null" /* raw JSON */,
    const std::string& mode = "size",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "height", static_cast<int64_t>(height));
  detail::Attr(&j, "width", static_cast<int64_t>(width));
  detail::Attr(&j, "scale_height", detail::Raw{scale_height});
  detail::Attr(&j, "scale_width", detail::Raw{scale_width});
  detail::Attr(&j, "mode", mode);
  std::vector<Symbol> ins{x};
  return Symbol::Create("BilinearResize2D", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BilinearSampler: positional inputs (data, grid)
inline Symbol BilinearSampler(
    const std::string& name,
    Symbol data,
    Symbol grid,
    bool cudnn_off = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "cudnn_off", cudnn_off);
  std::vector<Symbol> ins{data, grid};
  return Symbol::Create("BilinearSampler", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// BlockGrad: positional inputs (x)
inline Symbol BlockGrad(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("BlockGrad", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// CTCLoss: positional inputs (data, label, data_lengths, label_lengths)
inline Symbol CTCLoss(
    const std::string& name,
    Symbol data,
    Symbol label,
    Symbol data_lengths = Symbol(),
    Symbol label_lengths = Symbol(),
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string& blank_label = "first",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "use_data_lengths", use_data_lengths);
  detail::Attr(&j, "use_label_lengths", use_label_lengths);
  detail::Attr(&j, "blank_label", blank_label);
  std::vector<Symbol> ins{data, label, data_lengths, label_lengths};
  return Symbol::Create("CTCLoss", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Convolution: positional inputs (x, weight, bias)
inline Symbol Convolution(
    const std::string& name,
    Symbol x,
    Symbol weight,
    Symbol bias = Symbol(),
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& dilate = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    int64_t num_filter = 0,
    int64_t num_group = 1,
    bool no_bias = false,
    const std::string& layout = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "dilate", detail::Raw{dilate});
  detail::Attr(&j, "pad", detail::Raw{pad});
  detail::Attr(&j, "num_filter", static_cast<int64_t>(num_filter));
  detail::Attr(&j, "num_group", static_cast<int64_t>(num_group));
  detail::Attr(&j, "no_bias", no_bias);
  detail::Attr(&j, "layout", detail::Raw{layout});
  std::vector<Symbol> ins{x, weight, bias};
  return Symbol::Create("Convolution", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Correlation: positional inputs (data1, data2)
inline Symbol Correlation(
    const std::string& name,
    Symbol data1,
    Symbol data2,
    int64_t kernel_size = 1,
    int64_t max_displacement = 1,
    int64_t stride1 = 1,
    int64_t stride2 = 1,
    int64_t pad_size = 0,
    bool is_multiply = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel_size", static_cast<int64_t>(kernel_size));
  detail::Attr(&j, "max_displacement", static_cast<int64_t>(max_displacement));
  detail::Attr(&j, "stride1", static_cast<int64_t>(stride1));
  detail::Attr(&j, "stride2", static_cast<int64_t>(stride2));
  detail::Attr(&j, "pad_size", static_cast<int64_t>(pad_size));
  detail::Attr(&j, "is_multiply", is_multiply);
  std::vector<Symbol> ins{data1, data2};
  return Symbol::Create("Correlation", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Custom: positional inputs (variadic)
inline Symbol Custom(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& op_type = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "op_type", detail::Raw{op_type});
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("Custom", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Deconvolution: positional inputs (x, weight, bias)
inline Symbol Deconvolution(
    const std::string& name,
    Symbol x,
    Symbol weight,
    Symbol bias = Symbol(),
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& dilate = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    const std::string& adj = "null" /* raw JSON */,
    int64_t num_filter = 0,
    int64_t num_group = 1,
    bool no_bias = false,
    const std::string& target_shape = "null" /* raw JSON */,
    const std::string& layout = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "dilate", detail::Raw{dilate});
  detail::Attr(&j, "pad", detail::Raw{pad});
  detail::Attr(&j, "adj", detail::Raw{adj});
  detail::Attr(&j, "num_filter", static_cast<int64_t>(num_filter));
  detail::Attr(&j, "num_group", static_cast<int64_t>(num_group));
  detail::Attr(&j, "no_bias", no_bias);
  detail::Attr(&j, "target_shape", detail::Raw{target_shape});
  detail::Attr(&j, "layout", detail::Raw{layout});
  std::vector<Symbol> ins{x, weight, bias};
  return Symbol::Create("Deconvolution", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// DeformableConvolution: positional inputs (data, offset, weight, bias)
inline Symbol DeformableConvolution(
    const std::string& name,
    Symbol data,
    Symbol offset,
    Symbol weight,
    Symbol bias = Symbol(),
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& num_filter = "null" /* raw JSON */,
    const std::vector<int64_t>& stride = {1, 1},
    const std::vector<int64_t>& dilate = {1, 1},
    const std::vector<int64_t>& pad = {0, 0},
    int64_t num_group = 1,
    int64_t num_deformable_group = 1,
    bool no_bias = false,
    int64_t workspace = 0,
    const std::string& layout = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "num_filter", detail::Raw{num_filter});
  detail::Attr(&j, "stride", stride);
  detail::Attr(&j, "dilate", dilate);
  detail::Attr(&j, "pad", pad);
  detail::Attr(&j, "num_group", static_cast<int64_t>(num_group));
  detail::Attr(&j, "num_deformable_group", static_cast<int64_t>(num_deformable_group));
  detail::Attr(&j, "no_bias", no_bias);
  detail::Attr(&j, "workspace", static_cast<int64_t>(workspace));
  detail::Attr(&j, "layout", detail::Raw{layout});
  std::vector<Symbol> ins{data, offset, weight, bias};
  return Symbol::Create("DeformableConvolution", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Dropout: positional inputs (x, key)
inline Symbol Dropout(
    const std::string& name,
    Symbol x,
    Symbol key = Symbol(),
    double p = 0.5,
    const std::string& mode = "training",
    const std::vector<int64_t>& axes = {},
    bool training = false,
    bool cudnn_off = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "p", p);
  detail::Attr(&j, "mode", mode);
  detail::Attr(&j, "axes", axes);
  detail::Attr(&j, "training", training);
  detail::Attr(&j, "cudnn_off", cudnn_off);
  std::vector<Symbol> ins{x, key};
  return Symbol::Create("Dropout", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Embedding: positional inputs (indices, weight)
inline Symbol Embedding(
    const std::string& name,
    Symbol indices,
    Symbol weight,
    int64_t input_dim = 0,
    int64_t output_dim = 0,
    const std::string& dtype = "float32",
    bool sparse_grad = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "input_dim", static_cast<int64_t>(input_dim));
  detail::Attr(&j, "output_dim", static_cast<int64_t>(output_dim));
  detail::Attr(&j, "dtype", dtype);
  detail::Attr(&j, "sparse_grad", sparse_grad);
  std::vector<Symbol> ins{indices, weight};
  return Symbol::Create("Embedding", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// FullyConnected: positional inputs (x, weight, bias)
inline Symbol FullyConnected(
    const std::string& name,
    Symbol x,
    Symbol weight,
    Symbol bias = Symbol(),
    int64_t num_hidden = 0,
    bool no_bias = false,
    bool flatten = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_hidden", static_cast<int64_t>(num_hidden));
  detail::Attr(&j, "no_bias", no_bias);
  detail::Attr(&j, "flatten", flatten);
  std::vector<Symbol> ins{x, weight, bias};
  return Symbol::Create("FullyConnected", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// GridGenerator: positional inputs (data)
inline Symbol GridGenerator(
    const std::string& name,
    Symbol data,
    const std::string& transform_type = "affine",
    const std::vector<int64_t>& target_shape = {0, 0},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transform_type", transform_type);
  detail::Attr(&j, "target_shape", target_shape);
  std::vector<Symbol> ins{data};
  return Symbol::Create("GridGenerator", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// GroupNorm: positional inputs (x, gamma, beta)
inline Symbol GroupNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    int64_t num_groups = 1,
    double eps = 1e-05,
    bool output_mean_var = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_groups", static_cast<int64_t>(num_groups));
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "output_mean_var", output_mean_var);
  std::vector<Symbol> ins{x, gamma, beta};
  return Symbol::Create("GroupNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// IdentityAttachKLSparseReg: positional inputs (data, moving_avg)
inline Symbol IdentityAttachKLSparseReg(
    const std::string& name,
    Symbol data,
    Symbol moving_avg = Symbol(),
    double sparseness_target = 0.1,
    double penalty = 0.001,
    double momentum = 0.9,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "sparseness_target", sparseness_target);
  detail::Attr(&j, "penalty", penalty);
  detail::Attr(&j, "momentum", momentum);
  std::vector<Symbol> ins{data, moving_avg};
  return Symbol::Create("IdentityAttachKLSparseReg", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// InstanceNorm: positional inputs (x, gamma, beta)
inline Symbol InstanceNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    double eps = 0.001,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eps", eps);
  std::vector<Symbol> ins{x, gamma, beta};
  return Symbol::Create("InstanceNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// L2Normalization: positional inputs (x)
inline Symbol L2Normalization(
    const std::string& name,
    Symbol x,
    double eps = 1e-10,
    const std::string& mode = "instance",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "mode", mode);
  std::vector<Symbol> ins{x};
  return Symbol::Create("L2Normalization", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// LRN: positional inputs (x)
inline Symbol LRN(
    const std::string& name,
    Symbol x,
    int64_t nsize = 5,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "nsize", static_cast<int64_t>(nsize));
  detail::Attr(&j, "alpha", alpha);
  detail::Attr(&j, "beta", beta);
  detail::Attr(&j, "knorm", knorm);
  std::vector<Symbol> ins{x};
  return Symbol::Create("LRN", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// LayerNorm: positional inputs (x, gamma, beta)
inline Symbol LayerNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    int64_t axis = -1,
    double eps = 1e-05,
    bool output_mean_var = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "output_mean_var", output_mean_var);
  std::vector<Symbol> ins{x, gamma, beta};
  return Symbol::Create("LayerNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// LinearRegressionOutput: positional inputs (data, label)
inline Symbol LinearRegressionOutput(
    const std::string& name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "grad_scale", grad_scale);
  std::vector<Symbol> ins{data, label};
  return Symbol::Create("LinearRegressionOutput", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// LogisticRegressionOutput: positional inputs (data, label)
inline Symbol LogisticRegressionOutput(
    const std::string& name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "grad_scale", grad_scale);
  std::vector<Symbol> ins{data, label};
  return Symbol::Create("LogisticRegressionOutput", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// MAERegressionOutput: positional inputs (data, label)
inline Symbol MAERegressionOutput(
    const std::string& name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "grad_scale", grad_scale);
  std::vector<Symbol> ins{data, label};
  return Symbol::Create("MAERegressionOutput", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// MakeLoss: positional inputs (data)
inline Symbol MakeLoss(
    const std::string& name,
    Symbol data,
    double grad_scale = 1.0,
    double valid_thresh = 0.0,
    const std::string& normalization = "null",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "grad_scale", grad_scale);
  detail::Attr(&j, "valid_thresh", valid_thresh);
  detail::Attr(&j, "normalization", normalization);
  std::vector<Symbol> ins{data};
  return Symbol::Create("MakeLoss", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// MultiBoxDetection: positional inputs (cls_prob, loc_pred, anchor)
inline Symbol MultiBoxDetection(
    const std::string& name,
    Symbol cls_prob,
    Symbol loc_pred,
    Symbol anchor,
    bool clip = true,
    double threshold = 0.01,
    int64_t background_id = 0,
    double nms_threshold = 0.5,
    bool force_suppress = false,
    const std::vector<double>& variances = {0.1, 0.1, 0.2, 0.2},
    int64_t nms_topk = -1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "clip", clip);
  detail::Attr(&j, "threshold", threshold);
  detail::Attr(&j, "background_id", static_cast<int64_t>(background_id));
  detail::Attr(&j, "nms_threshold", nms_threshold);
  detail::Attr(&j, "force_suppress", force_suppress);
  detail::Attr(&j, "variances", variances);
  detail::Attr(&j, "nms_topk", static_cast<int64_t>(nms_topk));
  std::vector<Symbol> ins{cls_prob, loc_pred, anchor};
  return Symbol::Create("MultiBoxDetection", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// MultiBoxPrior: positional inputs (data)
inline Symbol MultiBoxPrior(
    const std::string& name,
    Symbol data,
    const std::vector<double>& sizes = {1.0},
    const std::vector<double>& ratios = {1.0},
    bool clip = false,
    const std::vector<double>& steps = {-1.0, -1.0},
    const std::vector<double>& offsets = {0.5, 0.5},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "sizes", sizes);
  detail::Attr(&j, "ratios", ratios);
  detail::Attr(&j, "clip", clip);
  detail::Attr(&j, "steps", steps);
  detail::Attr(&j, "offsets", offsets);
  std::vector<Symbol> ins{data};
  return Symbol::Create("MultiBoxPrior", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// MultiBoxTarget: positional inputs (anchor, label, cls_pred)
inline Symbol MultiBoxTarget(
    const std::string& name,
    Symbol anchor,
    Symbol label,
    Symbol cls_pred,
    double overlap_threshold = 0.5,
    double ignore_label = -1.0,
    double negative_mining_ratio = -1.0,
    double negative_mining_thresh = 0.5,
    int64_t minimum_negative_samples = 0,
    const std::vector<double>& variances = {0.1, 0.1, 0.2, 0.2},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "overlap_threshold", overlap_threshold);
  detail::Attr(&j, "ignore_label", ignore_label);
  detail::Attr(&j, "negative_mining_ratio", negative_mining_ratio);
  detail::Attr(&j, "negative_mining_thresh", negative_mining_thresh);
  detail::Attr(&j, "minimum_negative_samples", static_cast<int64_t>(minimum_negative_samples));
  detail::Attr(&j, "variances", variances);
  std::vector<Symbol> ins{anchor, label, cls_pred};
  return Symbol::Create("MultiBoxTarget", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Pooling: positional inputs (x)
inline Symbol Pooling(
    const std::string& name,
    Symbol x,
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& pool_type = "max",
    bool global_pool = false,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    const std::string& pooling_convention = "valid",
    bool count_include_pad = true,
    bool cudnn_off = false,
    const std::string& layout = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "pool_type", pool_type);
  detail::Attr(&j, "global_pool", global_pool);
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "pad", detail::Raw{pad});
  detail::Attr(&j, "pooling_convention", pooling_convention);
  detail::Attr(&j, "count_include_pad", count_include_pad);
  detail::Attr(&j, "cudnn_off", cudnn_off);
  detail::Attr(&j, "layout", detail::Raw{layout});
  std::vector<Symbol> ins{x};
  return Symbol::Create("Pooling", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// Proposal: positional inputs (cls_prob, bbox_pred, im_info)
inline Symbol Proposal(
    const std::string& name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int64_t rpn_pre_nms_top_n = 6000,
    int64_t rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int64_t rpn_min_size = 16,
    const std::vector<int64_t>& scales = {4, 8, 16, 32},
    const std::vector<double>& ratios = {0.5, 1.0, 2.0},
    int64_t feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "rpn_pre_nms_top_n", static_cast<int64_t>(rpn_pre_nms_top_n));
  detail::Attr(&j, "rpn_post_nms_top_n", static_cast<int64_t>(rpn_post_nms_top_n));
  detail::Attr(&j, "threshold", threshold);
  detail::Attr(&j, "rpn_min_size", static_cast<int64_t>(rpn_min_size));
  detail::Attr(&j, "scales", scales);
  detail::Attr(&j, "ratios", ratios);
  detail::Attr(&j, "feature_stride", static_cast<int64_t>(feature_stride));
  detail::Attr(&j, "output_score", output_score);
  detail::Attr(&j, "iou_loss", iou_loss);
  std::vector<Symbol> ins{cls_prob, bbox_pred, im_info};
  return Symbol::Create("Proposal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// RMSNorm: positional inputs (x, gamma)
inline Symbol RMSNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    int64_t axis = -1,
    double eps = 1e-06,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "eps", eps);
  std::vector<Symbol> ins{x, gamma};
  return Symbol::Create("RMSNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// RNN: positional inputs (x, params, state, state_cell)
inline Symbol RNN(
    const std::string& name,
    Symbol x,
    Symbol params,
    Symbol state,
    Symbol state_cell = Symbol(),
    int64_t state_size = 0,
    int64_t num_layers = 1,
    bool bidirectional = false,
    const std::string& mode = "lstm",
    double p = 0.0,
    bool state_outputs = true,
    const std::string& projection_size = "null" /* raw JSON */,
    bool use_sequence_length = false,
    const std::string& lstm_state_clip_min = "null" /* raw JSON */,
    const std::string& lstm_state_clip_max = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "state_size", static_cast<int64_t>(state_size));
  detail::Attr(&j, "num_layers", static_cast<int64_t>(num_layers));
  detail::Attr(&j, "bidirectional", bidirectional);
  detail::Attr(&j, "mode", mode);
  detail::Attr(&j, "p", p);
  detail::Attr(&j, "state_outputs", state_outputs);
  detail::Attr(&j, "projection_size", detail::Raw{projection_size});
  detail::Attr(&j, "use_sequence_length", use_sequence_length);
  detail::Attr(&j, "lstm_state_clip_min", detail::Raw{lstm_state_clip_min});
  detail::Attr(&j, "lstm_state_clip_max", detail::Raw{lstm_state_clip_max});
  std::vector<Symbol> ins{x, params, state, state_cell};
  return Symbol::Create("RNN", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ROIPooling: positional inputs (data, rois)
inline Symbol ROIPooling(
    const std::string& name,
    Symbol data,
    Symbol rois,
    const std::vector<int64_t>& pooled_size = {7, 7},
    double spatial_scale = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "pooled_size", pooled_size);
  detail::Attr(&j, "spatial_scale", spatial_scale);
  std::vector<Symbol> ins{data, rois};
  return Symbol::Create("ROIPooling", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// SVMOutput: positional inputs (data, label)
inline Symbol SVMOutput(
    const std::string& name,
    Symbol data,
    Symbol label,
    double margin = 1.0,
    double regularization_coefficient = 1.0,
    bool use_linear = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "margin", margin);
  detail::Attr(&j, "regularization_coefficient", regularization_coefficient);
  detail::Attr(&j, "use_linear", use_linear);
  std::vector<Symbol> ins{data, label};
  return Symbol::Create("SVMOutput", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// SoftmaxActivation: positional inputs (x)
inline Symbol SoftmaxActivation(
    const std::string& name,
    Symbol x,
    const std::string& mode = "instance",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "mode", mode);
  std::vector<Symbol> ins{x};
  return Symbol::Create("SoftmaxActivation", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// SoftmaxOutput: positional inputs (x, label)
inline Symbol SoftmaxOutput(
    const std::string& name,
    Symbol x,
    Symbol label,
    double grad_scale = 1.0,
    double ignore_label = -1.0,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string& normalization = "null",
    bool out_grad = false,
    double smooth_alpha = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "grad_scale", grad_scale);
  detail::Attr(&j, "ignore_label", ignore_label);
  detail::Attr(&j, "multi_output", multi_output);
  detail::Attr(&j, "use_ignore", use_ignore);
  detail::Attr(&j, "preserve_shape", preserve_shape);
  detail::Attr(&j, "normalization", normalization);
  detail::Attr(&j, "out_grad", out_grad);
  detail::Attr(&j, "smooth_alpha", smooth_alpha);
  std::vector<Symbol> ins{x, label};
  return Symbol::Create("SoftmaxOutput", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// SpatialTransformer: positional inputs (data, loc)
inline Symbol SpatialTransformer(
    const std::string& name,
    Symbol data,
    Symbol loc,
    const std::vector<int64_t>& target_shape = {0, 0},
    const std::string& transform_type = "affine",
    const std::string& sampler_type = "bilinear",
    bool cudnn_off = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "target_shape", target_shape);
  detail::Attr(&j, "transform_type", transform_type);
  detail::Attr(&j, "sampler_type", sampler_type);
  detail::Attr(&j, "cudnn_off", cudnn_off);
  std::vector<Symbol> ins{data, loc};
  return Symbol::Create("SpatialTransformer", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// SyncBatchNorm: positional inputs (x, gamma, beta, moving_mean, moving_var)
inline Symbol SyncBatchNorm(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int64_t ndev = 1,
    const std::string& key = "",
    const std::string& axis_name = "null" /* raw JSON */,
    bool training = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "fix_gamma", fix_gamma);
  detail::Attr(&j, "use_global_stats", use_global_stats);
  detail::Attr(&j, "output_mean_var", output_mean_var);
  detail::Attr(&j, "ndev", static_cast<int64_t>(ndev));
  detail::Attr(&j, "key", key);
  detail::Attr(&j, "axis_name", detail::Raw{axis_name});
  detail::Attr(&j, "training", training);
  std::vector<Symbol> ins{x, gamma, beta, moving_mean, moving_var};
  return Symbol::Create("SyncBatchNorm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// UpSampling: positional inputs (x)
inline Symbol UpSampling(
    const std::string& name,
    Symbol x,
    int64_t scale = 2,
    const std::string& sample_type = "nearest",
    int64_t num_args = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scale", static_cast<int64_t>(scale));
  detail::Attr(&j, "sample_type", sample_type);
  detail::Attr(&j, "num_args", static_cast<int64_t>(num_args));
  std::vector<Symbol> ins{x};
  return Symbol::Create("UpSampling", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _CachedSubgraph: positional inputs (variadic)
inline Symbol _CachedSubgraph(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& sym = "null" /* raw JSON */,
    const std::string& arg_names = "null" /* raw JSON */,
    const std::string& backend = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "sym", detail::Raw{sym});
  detail::Attr(&j, "arg_names", detail::Raw{arg_names});
  detail::Attr(&j, "backend", detail::Raw{backend});
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("_CachedSubgraph", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_ROIAlign: positional inputs (data, rois)
inline Symbol _contrib_ROIAlign(
    const std::string& name,
    Symbol data,
    Symbol rois,
    const std::vector<int64_t>& pooled_size = {7, 7},
    double spatial_scale = 1.0,
    int64_t sample_ratio = 2,
    bool position_sensitive = false,
    bool aligned = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "pooled_size", pooled_size);
  detail::Attr(&j, "spatial_scale", spatial_scale);
  detail::Attr(&j, "sample_ratio", static_cast<int64_t>(sample_ratio));
  detail::Attr(&j, "position_sensitive", position_sensitive);
  detail::Attr(&j, "aligned", aligned);
  std::vector<Symbol> ins{data, rois};
  return Symbol::Create("_contrib_ROIAlign", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_RROIAlign: positional inputs (data, rois)
inline Symbol _contrib_RROIAlign(
    const std::string& name,
    Symbol data,
    Symbol rois,
    const std::string& pooled_size = "null" /* raw JSON */,
    const std::string& spatial_scale = "null" /* raw JSON */,
    int64_t sampling_ratio = 2,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "pooled_size", detail::Raw{pooled_size});
  detail::Attr(&j, "spatial_scale", detail::Raw{spatial_scale});
  detail::Attr(&j, "sampling_ratio", static_cast<int64_t>(sampling_ratio));
  std::vector<Symbol> ins{data, rois};
  return Symbol::Create("_contrib_RROIAlign", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_SparseEmbedding: positional inputs (indices, weight)
inline Symbol _contrib_SparseEmbedding(
    const std::string& name,
    Symbol indices,
    Symbol weight,
    int64_t input_dim = 0,
    int64_t output_dim = 0,
    const std::string& dtype = "float32",
    bool deterministic = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "input_dim", static_cast<int64_t>(input_dim));
  detail::Attr(&j, "output_dim", static_cast<int64_t>(output_dim));
  detail::Attr(&j, "dtype", dtype);
  detail::Attr(&j, "deterministic", deterministic);
  std::vector<Symbol> ins{indices, weight};
  return Symbol::Create("_contrib_SparseEmbedding", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_box_decode: positional inputs (data, anchors)
inline Symbol _contrib_box_decode(
    const std::string& name,
    Symbol data,
    Symbol anchors,
    double std0 = 0.1,
    double std1 = 0.1,
    double std2 = 0.2,
    double std3 = 0.2,
    double clip = -1.0,
    const std::string& format = "corner",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "std0", std0);
  detail::Attr(&j, "std1", std1);
  detail::Attr(&j, "std2", std2);
  detail::Attr(&j, "std3", std3);
  detail::Attr(&j, "clip", clip);
  detail::Attr(&j, "format", format);
  std::vector<Symbol> ins{data, anchors};
  return Symbol::Create("_contrib_box_decode", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_box_encode: positional inputs (samples, matches, anchors, refs)
inline Symbol _contrib_box_encode(
    const std::string& name,
    Symbol samples,
    Symbol matches,
    Symbol anchors,
    Symbol refs,
    const std::vector<double>& means = {0.0, 0.0, 0.0, 0.0},
    const std::vector<double>& stds = {0.1, 0.1, 0.2, 0.2},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "means", means);
  detail::Attr(&j, "stds", stds);
  std::vector<Symbol> ins{samples, matches, anchors, refs};
  return Symbol::Create("_contrib_box_encode", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_box_iou: positional inputs (lhs, rhs)
inline Symbol _contrib_box_iou(
    const std::string& name,
    Symbol lhs,
    Symbol rhs,
    const std::string& format = "corner",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "format", format);
  std::vector<Symbol> ins{lhs, rhs};
  return Symbol::Create("_contrib_box_iou", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_box_nms: positional inputs (data)
inline Symbol _contrib_box_nms(
    const std::string& name,
    Symbol data,
    double overlap_thresh = 0.5,
    double valid_thresh = 0.0,
    int64_t topk = -1,
    int64_t coord_start = 2,
    int64_t score_index = 1,
    int64_t id_index = 0,
    int64_t background_id = -1,
    bool force_suppress = false,
    const std::string& in_format = "corner",
    const std::string& out_format = "corner",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "overlap_thresh", overlap_thresh);
  detail::Attr(&j, "valid_thresh", valid_thresh);
  detail::Attr(&j, "topk", static_cast<int64_t>(topk));
  detail::Attr(&j, "coord_start", static_cast<int64_t>(coord_start));
  detail::Attr(&j, "score_index", static_cast<int64_t>(score_index));
  detail::Attr(&j, "id_index", static_cast<int64_t>(id_index));
  detail::Attr(&j, "background_id", static_cast<int64_t>(background_id));
  detail::Attr(&j, "force_suppress", force_suppress);
  detail::Attr(&j, "in_format", in_format);
  detail::Attr(&j, "out_format", out_format);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_box_nms", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_calibrate_entropy: positional inputs (hist, hist_edges)
inline Symbol _contrib_calibrate_entropy(
    const std::string& name,
    Symbol hist,
    Symbol hist_edges,
    int64_t num_quantized_bins = 255,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_quantized_bins", static_cast<int64_t>(num_quantized_bins));
  std::vector<Symbol> ins{hist, hist_edges};
  return Symbol::Create("_contrib_calibrate_entropy", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_count_sketch: positional inputs (data, h, s)
inline Symbol _contrib_count_sketch(
    const std::string& name,
    Symbol data,
    Symbol h,
    Symbol s,
    int64_t out_dim = 0,
    int64_t processing_batch_size = 32,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "out_dim", static_cast<int64_t>(out_dim));
  detail::Attr(&j, "processing_batch_size", static_cast<int64_t>(processing_batch_size));
  std::vector<Symbol> ins{data, h, s};
  return Symbol::Create("_contrib_count_sketch", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_dequantize: positional inputs (data, min_range, max_range)
inline Symbol _contrib_dequantize(
    const std::string& name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string& out_type = "float32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "out_type", out_type);
  std::vector<Symbol> ins{data, min_range, max_range};
  return Symbol::Create("_contrib_dequantize", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_div_sqrt_dim: positional inputs (x)
inline Symbol _contrib_div_sqrt_dim(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("_contrib_div_sqrt_dim", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_fft: positional inputs (data)
inline Symbol _contrib_fft(
    const std::string& name,
    Symbol data,
    int64_t compute_size = 128,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "compute_size", static_cast<int64_t>(compute_size));
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_fft", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_getnnz: positional inputs (data)
inline Symbol _contrib_getnnz(
    const std::string& name,
    Symbol data,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_getnnz", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_gradientmultiplier: positional inputs (data)
inline Symbol _contrib_gradientmultiplier(
    const std::string& name,
    Symbol data,
    double scalar = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_gradientmultiplier", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_hawkes_ll: positional inputs (lda, alpha, beta, state, lags, marks, valid_length, max_time)
inline Symbol _contrib_hawkes_ll(
    const std::string& name,
    Symbol lda,
    Symbol alpha,
    Symbol beta,
    Symbol state,
    Symbol lags,
    Symbol marks,
    Symbol valid_length,
    Symbol max_time,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{lda, alpha, beta, state, lags, marks, valid_length, max_time};
  return Symbol::Create("_contrib_hawkes_ll", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_hawkesll: positional inputs (lda, alpha, beta, state, lags, marks, valid_length, max_time)
inline Symbol _contrib_hawkesll(
    const std::string& name,
    Symbol lda,
    Symbol alpha,
    Symbol beta,
    Symbol state,
    Symbol lags,
    Symbol marks,
    Symbol valid_length,
    Symbol max_time,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{lda, alpha, beta, state, lags, marks, valid_length, max_time};
  return Symbol::Create("_contrib_hawkesll", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_ifft: positional inputs (data)
inline Symbol _contrib_ifft(
    const std::string& name,
    Symbol data,
    int64_t compute_size = 128,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "compute_size", static_cast<int64_t>(compute_size));
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_ifft", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_index_array: positional inputs (data)
inline Symbol _contrib_index_array(
    const std::string& name,
    Symbol data,
    const std::string& axes = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axes", detail::Raw{axes});
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_index_array", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_index_copy: positional inputs (old, index, new)
inline Symbol _contrib_index_copy(
    const std::string& name,
    Symbol old,
    Symbol index,
    Symbol new_,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{old, index, new_};
  return Symbol::Create("_contrib_index_copy", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_interleaved_matmul_encdec_qk: positional inputs (q, kv)
inline Symbol _contrib_interleaved_matmul_encdec_qk(
    const std::string& name,
    Symbol q,
    Symbol kv,
    const std::string& heads = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  std::vector<Symbol> ins{q, kv};
  return Symbol::Create("_contrib_interleaved_matmul_encdec_qk", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_interleaved_matmul_encdec_valatt: positional inputs (kv, att)
inline Symbol _contrib_interleaved_matmul_encdec_valatt(
    const std::string& name,
    Symbol kv,
    Symbol att,
    const std::string& heads = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  std::vector<Symbol> ins{kv, att};
  return Symbol::Create("_contrib_interleaved_matmul_encdec_valatt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_interleaved_matmul_selfatt_qk: positional inputs (qkv)
inline Symbol _contrib_interleaved_matmul_selfatt_qk(
    const std::string& name,
    Symbol qkv,
    const std::string& heads = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  std::vector<Symbol> ins{qkv};
  return Symbol::Create("_contrib_interleaved_matmul_selfatt_qk", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_interleaved_matmul_selfatt_valatt: positional inputs (qkv, att)
inline Symbol _contrib_interleaved_matmul_selfatt_valatt(
    const std::string& name,
    Symbol qkv,
    Symbol att,
    const std::string& heads = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  std::vector<Symbol> ins{qkv, att};
  return Symbol::Create("_contrib_interleaved_matmul_selfatt_valatt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_mrcnn_mask_target: positional inputs (rois, gt_masks, matches, cls_targets)
inline Symbol _contrib_mrcnn_mask_target(
    const std::string& name,
    Symbol rois,
    Symbol gt_masks,
    Symbol matches,
    Symbol cls_targets,
    int64_t num_rois = 0,
    int64_t num_classes = 0,
    const std::vector<int64_t>& mask_size = {14, 14},
    int64_t sample_ratio = 2,
    bool aligned = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_rois", static_cast<int64_t>(num_rois));
  detail::Attr(&j, "num_classes", static_cast<int64_t>(num_classes));
  detail::Attr(&j, "mask_size", mask_size);
  detail::Attr(&j, "sample_ratio", static_cast<int64_t>(sample_ratio));
  detail::Attr(&j, "aligned", aligned);
  std::vector<Symbol> ins{rois, gt_masks, matches, cls_targets};
  return Symbol::Create("_contrib_mrcnn_mask_target", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quadratic: positional inputs (data)
inline Symbol _contrib_quadratic(
    const std::string& name,
    Symbol data,
    double a = 0.0,
    double b = 0.0,
    double c = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "a", a);
  detail::Attr(&j, "b", b);
  detail::Attr(&j, "c", c);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_quadratic", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantize: positional inputs (data, min_range, max_range)
inline Symbol _contrib_quantize(
    const std::string& name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string& out_type = "uint8",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "out_type", out_type);
  std::vector<Symbol> ins{data, min_range, max_range};
  return Symbol::Create("_contrib_quantize", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantize_v2: positional inputs (data)
inline Symbol _contrib_quantize_v2(
    const std::string& name,
    Symbol data,
    const std::string& out_type = "int8",
    const std::string& min_calib_range = "null" /* raw JSON */,
    const std::string& max_calib_range = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "out_type", out_type);
  detail::Attr(&j, "min_calib_range", detail::Raw{min_calib_range});
  detail::Attr(&j, "max_calib_range", detail::Raw{max_calib_range});
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_quantize_v2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_act: positional inputs (x, min_x, max_x)
inline Symbol _contrib_quantized_act(
    const std::string& name,
    Symbol x,
    Symbol min_x,
    Symbol max_x,
    const std::string& act_type = "relu",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "act_type", act_type);
  std::vector<Symbol> ins{x, min_x, max_x};
  return Symbol::Create("_contrib_quantized_act", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_batch_norm: positional inputs (data, gamma, beta, moving_mean, moving_var, min_data, max_data)
inline Symbol _contrib_quantized_batch_norm(
    const std::string& name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    Symbol min_data,
    Symbol max_data,
    double eps = 0.001,
    const std::string& min_calib_range = "null" /* raw JSON */,
    const std::string& max_calib_range = "null" /* raw JSON */,
    int64_t axis = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eps", eps);
  detail::Attr(&j, "min_calib_range", detail::Raw{min_calib_range});
  detail::Attr(&j, "max_calib_range", detail::Raw{max_calib_range});
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{data, gamma, beta, moving_mean, moving_var, min_data, max_data};
  return Symbol::Create("_contrib_quantized_batch_norm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_concat: positional inputs (variadic)
inline Symbol _contrib_quantized_concat(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t dim = 1,
    int64_t num_args = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "dim", static_cast<int64_t>(dim));
  detail::Attr(&j, "num_args", static_cast<int64_t>(num_args));
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("_contrib_quantized_concat", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_conv: positional inputs (x, weight, min_x, max_x, min_w, max_w)
inline Symbol _contrib_quantized_conv(
    const std::string& name,
    Symbol x,
    Symbol weight,
    Symbol min_x,
    Symbol max_x,
    Symbol min_w,
    Symbol max_w,
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& dilate = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    int64_t num_filter = 0,
    int64_t num_group = 1,
    const std::string& layout = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "dilate", detail::Raw{dilate});
  detail::Attr(&j, "pad", detail::Raw{pad});
  detail::Attr(&j, "num_filter", static_cast<int64_t>(num_filter));
  detail::Attr(&j, "num_group", static_cast<int64_t>(num_group));
  detail::Attr(&j, "layout", detail::Raw{layout});
  std::vector<Symbol> ins{x, weight, min_x, max_x, min_w, max_w};
  return Symbol::Create("_contrib_quantized_conv", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_elemwise_add: positional inputs (a, b, min_a, max_a, min_b, max_b)
inline Symbol _contrib_quantized_elemwise_add(
    const std::string& name,
    Symbol a,
    Symbol b,
    Symbol min_a,
    Symbol max_a,
    Symbol min_b,
    Symbol max_b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b, min_a, max_a, min_b, max_b};
  return Symbol::Create("_contrib_quantized_elemwise_add", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_elemwise_mul: positional inputs (lhs, rhs, min_lhs, max_lhs, min_rhs, max_rhs)
inline Symbol _contrib_quantized_elemwise_mul(
    const std::string& name,
    Symbol lhs,
    Symbol rhs,
    Symbol min_lhs,
    Symbol max_lhs,
    Symbol min_rhs,
    Symbol max_rhs,
    const std::string& min_calib_range = "null" /* raw JSON */,
    const std::string& max_calib_range = "null" /* raw JSON */,
    bool enable_float_output = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_calib_range", detail::Raw{min_calib_range});
  detail::Attr(&j, "max_calib_range", detail::Raw{max_calib_range});
  detail::Attr(&j, "enable_float_output", enable_float_output);
  std::vector<Symbol> ins{lhs, rhs, min_lhs, max_lhs, min_rhs, max_rhs};
  return Symbol::Create("_contrib_quantized_elemwise_mul", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_embedding: positional inputs (data, weight, min_weight, max_weight)
inline Symbol _contrib_quantized_embedding(
    const std::string& name,
    Symbol data,
    Symbol weight,
    Symbol min_weight,
    Symbol max_weight,
    int64_t input_dim = 0,
    int64_t output_dim = 0,
    const std::string& dtype = "int8",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "input_dim", static_cast<int64_t>(input_dim));
  detail::Attr(&j, "output_dim", static_cast<int64_t>(output_dim));
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{data, weight, min_weight, max_weight};
  return Symbol::Create("_contrib_quantized_embedding", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_flatten: positional inputs (x, min_x, max_x)
inline Symbol _contrib_quantized_flatten(
    const std::string& name,
    Symbol x,
    Symbol min_x,
    Symbol max_x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, min_x, max_x};
  return Symbol::Create("_contrib_quantized_flatten", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_fully_connected: positional inputs (x, weight, min_x, max_x, min_w, max_w)
inline Symbol _contrib_quantized_fully_connected(
    const std::string& name,
    Symbol x,
    Symbol weight,
    Symbol min_x,
    Symbol max_x,
    Symbol min_w,
    Symbol max_w,
    int64_t num_hidden = 0,
    bool flatten = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_hidden", static_cast<int64_t>(num_hidden));
  detail::Attr(&j, "flatten", flatten);
  std::vector<Symbol> ins{x, weight, min_x, max_x, min_w, max_w};
  return Symbol::Create("_contrib_quantized_fully_connected", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_quantized_pooling: positional inputs (x, min_x, max_x)
inline Symbol _contrib_quantized_pooling(
    const std::string& name,
    Symbol x,
    Symbol min_x,
    Symbol max_x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, min_x, max_x};
  return Symbol::Create("_contrib_quantized_pooling", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_requantize: positional inputs (data, min_range, max_range)
inline Symbol _contrib_requantize(
    const std::string& name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string& min_calib_range = "null" /* raw JSON */,
    const std::string& max_calib_range = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_calib_range", detail::Raw{min_calib_range});
  detail::Attr(&j, "max_calib_range", detail::Raw{max_calib_range});
  std::vector<Symbol> ins{data, min_range, max_range};
  return Symbol::Create("_contrib_requantize", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_round_ste: positional inputs (data)
inline Symbol _contrib_round_ste(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_round_ste", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _contrib_sign_ste: positional inputs (data)
inline Symbol _contrib_sign_ste(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("_contrib_sign_ste", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _div_scalar: positional inputs (x)
inline Symbol _div_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_div_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _getitem: positional inputs (x)
inline Symbol _getitem(
    const std::string& name,
    Symbol x,
    const std::string& key = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "key", detail::Raw{key});
  std::vector<Symbol> ins{x};
  return Symbol::Create("_getitem", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _hypot_scalar: positional inputs (x)
inline Symbol _hypot_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_hypot_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_adjust_lighting: positional inputs (data)
inline Symbol _image_adjust_lighting(
    const std::string& name,
    Symbol data,
    const std::vector<double>& alpha = {0.0, 0.0, 0.0},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "alpha", alpha);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_adjust_lighting", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_crop: positional inputs (data)
inline Symbol _image_crop(
    const std::string& name,
    Symbol data,
    int64_t x = 0,
    int64_t y = 0,
    int64_t width = 1,
    int64_t height = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "x", static_cast<int64_t>(x));
  detail::Attr(&j, "y", static_cast<int64_t>(y));
  detail::Attr(&j, "width", static_cast<int64_t>(width));
  detail::Attr(&j, "height", static_cast<int64_t>(height));
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_crop", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_flip_left_right: positional inputs (data)
inline Symbol _image_flip_left_right(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_flip_left_right", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_flip_top_bottom: positional inputs (data)
inline Symbol _image_flip_top_bottom(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_flip_top_bottom", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_normalize: positional inputs (data)
inline Symbol _image_normalize(
    const std::string& name,
    Symbol data,
    const std::vector<double>& mean = {0.0},
    const std::vector<double>& std = {1.0},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "mean", mean);
  detail::Attr(&j, "std", std);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_normalize", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_brightness: positional inputs (data, key)
inline Symbol _image_random_brightness(
    const std::string& name,
    Symbol data,
    Symbol key,
    double min_factor = 0.0,
    double max_factor = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_factor", min_factor);
  detail::Attr(&j, "max_factor", max_factor);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_brightness", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_color_jitter: positional inputs (data, key)
inline Symbol _image_random_color_jitter(
    const std::string& name,
    Symbol data,
    Symbol key,
    double brightness = 0.0,
    double contrast = 0.0,
    double saturation = 0.0,
    double hue = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "brightness", brightness);
  detail::Attr(&j, "contrast", contrast);
  detail::Attr(&j, "saturation", saturation);
  detail::Attr(&j, "hue", hue);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_color_jitter", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_contrast: positional inputs (data, key)
inline Symbol _image_random_contrast(
    const std::string& name,
    Symbol data,
    Symbol key,
    double min_factor = 0.0,
    double max_factor = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_factor", min_factor);
  detail::Attr(&j, "max_factor", max_factor);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_contrast", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_flip_left_right: positional inputs (data, key)
inline Symbol _image_random_flip_left_right(
    const std::string& name,
    Symbol data,
    Symbol key,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_flip_left_right", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_flip_top_bottom: positional inputs (data, key)
inline Symbol _image_random_flip_top_bottom(
    const std::string& name,
    Symbol data,
    Symbol key,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_flip_top_bottom", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_hue: positional inputs (data, key)
inline Symbol _image_random_hue(
    const std::string& name,
    Symbol data,
    Symbol key,
    double min_factor = 0.0,
    double max_factor = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_factor", min_factor);
  detail::Attr(&j, "max_factor", max_factor);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_hue", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_lighting: positional inputs (data, key)
inline Symbol _image_random_lighting(
    const std::string& name,
    Symbol data,
    Symbol key,
    double alpha_std = 0.05,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "alpha_std", alpha_std);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_lighting", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_random_saturation: positional inputs (data, key)
inline Symbol _image_random_saturation(
    const std::string& name,
    Symbol data,
    Symbol key,
    double min_factor = 0.0,
    double max_factor = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "min_factor", min_factor);
  detail::Attr(&j, "max_factor", max_factor);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_image_random_saturation", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_resize: positional inputs (data)
inline Symbol _image_resize(
    const std::string& name,
    Symbol data,
    const std::vector<int64_t>& size = {0, 0},
    bool keep_ratio = false,
    int64_t interp = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "size", size);
  detail::Attr(&j, "keep_ratio", keep_ratio);
  detail::Attr(&j, "interp", static_cast<int64_t>(interp));
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_resize", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _image_to_tensor: positional inputs (data)
inline Symbol _image_to_tensor(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("_image_to_tensor", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _maximum_scalar: positional inputs (x)
inline Symbol _maximum_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_maximum_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _minimum_scalar: positional inputs (x)
inline Symbol _minimum_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_minimum_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _minus_scalar: positional inputs (x)
inline Symbol _minus_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_minus_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _mod_scalar: positional inputs (x)
inline Symbol _mod_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_mod_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _mul_scalar: positional inputs (x)
inline Symbol _mul_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_mul_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _npx_constraint_check: positional inputs (data)
inline Symbol _npx_constraint_check(
    const std::string& name,
    Symbol data,
    const std::string& msg = "Constraint violated.",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "msg", msg);
  std::vector<Symbol> ins{data};
  return Symbol::Create("_npx_constraint_check", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _plus_scalar: positional inputs (x)
inline Symbol _plus_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_plus_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _power_scalar: positional inputs (x)
inline Symbol _power_scalar(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("_power_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_bernoulli: positional inputs (key)
inline Symbol _random_bernoulli(
    const std::string& name,
    Symbol key,
    double p = 0.5,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "p", p);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_bernoulli", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_dirichlet: positional inputs (key, alpha)
inline Symbol _random_dirichlet(
    const std::string& name,
    Symbol key,
    Symbol alpha,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key, alpha};
  return Symbol::Create("_random_dirichlet", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_exponential: positional inputs (key)
inline Symbol _random_exponential(
    const std::string& name,
    Symbol key,
    double lam = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lam", lam);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_exponential", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_gamma: positional inputs (key)
inline Symbol _random_gamma(
    const std::string& name,
    Symbol key,
    double alpha = 1.0,
    double beta = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "alpha", alpha);
  detail::Attr(&j, "beta", beta);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_gamma", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_generalized_negative_binomial: positional inputs (key)
inline Symbol _random_generalized_negative_binomial(
    const std::string& name,
    Symbol key,
    double mu = 1.0,
    double alpha = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "mu", mu);
  detail::Attr(&j, "alpha", alpha);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_generalized_negative_binomial", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_negative_binomial: positional inputs (key)
inline Symbol _random_negative_binomial(
    const std::string& name,
    Symbol key,
    int64_t k = 1,
    double p = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "k", static_cast<int64_t>(k));
  detail::Attr(&j, "p", p);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_negative_binomial", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_normal: positional inputs (key)
inline Symbol _random_normal(
    const std::string& name,
    Symbol key,
    double loc = 0.0,
    double scale = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "loc", loc);
  detail::Attr(&j, "scale", scale);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_normal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_poisson: positional inputs (key)
inline Symbol _random_poisson(
    const std::string& name,
    Symbol key,
    double lam = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lam", lam);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_poisson", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_randint: positional inputs (key)
inline Symbol _random_randint(
    const std::string& name,
    Symbol key,
    int64_t low = 0,
    int64_t high = 1,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "int32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "low", static_cast<int64_t>(low));
  detail::Attr(&j, "high", static_cast<int64_t>(high));
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_randint", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _random_uniform: positional inputs (key)
inline Symbol _random_uniform(
    const std::string& name,
    Symbol key,
    double low = 0.0,
    double high = 1.0,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "low", low);
  detail::Attr(&j, "high", high);
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{key};
  return Symbol::Create("_random_uniform", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_exponential: positional inputs (lam, key)
inline Symbol _sample_exponential(
    const std::string& name,
    Symbol lam,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{lam, key};
  return Symbol::Create("_sample_exponential", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_gamma: positional inputs (alpha, beta, key)
inline Symbol _sample_gamma(
    const std::string& name,
    Symbol alpha,
    Symbol beta,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{alpha, beta, key};
  return Symbol::Create("_sample_gamma", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_generalized_negative_binomial: positional inputs (mu, alpha, key)
inline Symbol _sample_generalized_negative_binomial(
    const std::string& name,
    Symbol mu,
    Symbol alpha,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{mu, alpha, key};
  return Symbol::Create("_sample_generalized_negative_binomial", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_multinomial: positional inputs (data, key)
inline Symbol _sample_multinomial(
    const std::string& name,
    Symbol data,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    bool get_prob = false,
    const std::string& dtype = "int32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "get_prob", get_prob);
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_sample_multinomial", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_negative_binomial: positional inputs (k_param, p, key)
inline Symbol _sample_negative_binomial(
    const std::string& name,
    Symbol k_param,
    Symbol p,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{k_param, p, key};
  return Symbol::Create("_sample_negative_binomial", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_normal: positional inputs (mu, sigma, key)
inline Symbol _sample_normal(
    const std::string& name,
    Symbol mu,
    Symbol sigma,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{mu, sigma, key};
  return Symbol::Create("_sample_normal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_poisson: positional inputs (lam, key)
inline Symbol _sample_poisson(
    const std::string& name,
    Symbol lam,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{lam, key};
  return Symbol::Create("_sample_poisson", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_uniform: positional inputs (low, high, key)
inline Symbol _sample_uniform(
    const std::string& name,
    Symbol low,
    Symbol high,
    Symbol key,
    const std::vector<int64_t>& shape = {},
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", shape);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{low, high, key};
  return Symbol::Create("_sample_uniform", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _sample_unique_zipfian: positional inputs (key)
inline Symbol _sample_unique_zipfian(
    const std::string& name,
    Symbol key,
    int64_t range_max = 1,
    const std::vector<int64_t>& shape = {},
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "range_max", static_cast<int64_t>(range_max));
  detail::Attr(&j, "shape", shape);
  std::vector<Symbol> ins{key};
  return Symbol::Create("_sample_unique_zipfian", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _scatter_set_nd: positional inputs (lhs, data, indices)
inline Symbol _scatter_set_nd(
    const std::string& name,
    Symbol lhs,
    Symbol data,
    Symbol indices,
    const std::string& shape = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  std::vector<Symbol> ins{lhs, data, indices};
  return Symbol::Create("_scatter_set_nd", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// _shuffle: positional inputs (data, key)
inline Symbol _shuffle(
    const std::string& name,
    Symbol data,
    Symbol key,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data, key};
  return Symbol::Create("_shuffle", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// abs: positional inputs (x)
inline Symbol abs(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("abs", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// adam_update: positional inputs (weight, grad, mean, var)
inline Symbol adam_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    const std::string& lr = "null" /* raw JSON */,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "lazy_update", lazy_update);
  std::vector<Symbol> ins{weight, grad, mean, var};
  return Symbol::Create("adam_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// adamw_update: positional inputs (weight, grad, mean, var)
inline Symbol adamw_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    const std::string& lr = "null" /* raw JSON */,
    double eta = 1.0,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "eta", eta);
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mean, var};
  return Symbol::Create("adamw_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// add_n: positional inputs (variadic)
inline Symbol add_n(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("add_n", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// all_finite: positional inputs (data)
inline Symbol all_finite(
    const std::string& name,
    Symbol data,
    bool init_output = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "init_output", init_output);
  std::vector<Symbol> ins{data};
  return Symbol::Create("all_finite", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// allclose: positional inputs (a, b)
inline Symbol allclose(
    const std::string& name,
    Symbol a,
    Symbol b,
    double rtol = 1e-05,
    double atol = 1e-08,
    bool equal_nan = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "rtol", rtol);
  detail::Attr(&j, "atol", atol);
  detail::Attr(&j, "equal_nan", equal_nan);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("allclose", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// amp_cast: positional inputs (x)
inline Symbol amp_cast(
    const std::string& name,
    Symbol x,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x};
  return Symbol::Create("amp_cast", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// amp_multicast: positional inputs (variadic)
inline Symbol amp_multicast(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& num_outputs = "null" /* raw JSON */,
    bool cast_narrow = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_outputs", detail::Raw{num_outputs});
  detail::Attr(&j, "cast_narrow", cast_narrow);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("amp_multicast", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arange_like: positional inputs (x)
inline Symbol arange_like(
    const std::string& name,
    Symbol x,
    double start = 0.0,
    double step = 1.0,
    int64_t repeat = 1,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "start", start);
  detail::Attr(&j, "step", step);
  detail::Attr(&j, "repeat", static_cast<int64_t>(repeat));
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{x};
  return Symbol::Create("arange_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arccos: positional inputs (x)
inline Symbol arccos(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arccos", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arccosh: positional inputs (x)
inline Symbol arccosh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arccosh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arcsin: positional inputs (x)
inline Symbol arcsin(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arcsin", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arcsinh: positional inputs (x)
inline Symbol arcsinh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arcsinh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arctan: positional inputs (x)
inline Symbol arctan(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arctan", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arctan2: positional inputs (a, b)
inline Symbol arctan2(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("arctan2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// arctanh: positional inputs (x)
inline Symbol arctanh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("arctanh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// argmax: positional inputs (x)
inline Symbol argmax(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  std::vector<Symbol> ins{x};
  return Symbol::Create("argmax", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// argmax_channel: positional inputs (x)
inline Symbol argmax_channel(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("argmax_channel", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// argmin: positional inputs (x)
inline Symbol argmin(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  std::vector<Symbol> ins{x};
  return Symbol::Create("argmin", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// argsort: positional inputs (x)
inline Symbol argsort(
    const std::string& name,
    Symbol x,
    int64_t axis = -1,
    bool is_ascend = true,
    const std::string& dtype = "float32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "is_ascend", is_ascend);
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{x};
  return Symbol::Create("argsort", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// batch_dot: positional inputs (a, b)
inline Symbol batch_dot(
    const std::string& name,
    Symbol a,
    Symbol b,
    bool transpose_a = false,
    bool transpose_b = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose_a", transpose_a);
  detail::Attr(&j, "transpose_b", transpose_b);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("batch_dot", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// batch_take: positional inputs (x, indices)
inline Symbol batch_take(
    const std::string& name,
    Symbol x,
    Symbol indices,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, indices};
  return Symbol::Create("batch_take", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// bipartite_matching: positional inputs (dist)
inline Symbol bipartite_matching(
    const std::string& name,
    Symbol dist,
    const std::string& threshold = "null" /* raw JSON */,
    bool is_ascend = false,
    int64_t topk = -1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "threshold", detail::Raw{threshold});
  detail::Attr(&j, "is_ascend", is_ascend);
  detail::Attr(&j, "topk", static_cast<int64_t>(topk));
  std::vector<Symbol> ins{dist};
  return Symbol::Create("bipartite_matching", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// block_attention: positional inputs (q, k, v, positions, ctx_acc, ctx_max, ctx_sum)
inline Symbol block_attention(
    const std::string& name,
    Symbol q,
    Symbol k,
    Symbol v,
    Symbol positions,
    Symbol ctx_acc = Symbol(),
    Symbol ctx_max = Symbol(),
    Symbol ctx_sum = Symbol(),
    const std::string& heads = "null" /* raw JSON */,
    const std::string& kv_heads = "null" /* raw JSON */,
    int64_t block_length = 1,
    const std::string& sm_scale = "null" /* raw JSON */,
    const std::string& window = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  detail::Attr(&j, "kv_heads", detail::Raw{kv_heads});
  detail::Attr(&j, "block_length", static_cast<int64_t>(block_length));
  detail::Attr(&j, "sm_scale", detail::Raw{sm_scale});
  detail::Attr(&j, "window", detail::Raw{window});
  std::vector<Symbol> ins{q, k, v, positions, ctx_acc, ctx_max, ctx_sum};
  return Symbol::Create("block_attention", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// boolean_mask_dense: positional inputs (data, mask)
inline Symbol boolean_mask_dense(
    const std::string& name,
    Symbol data,
    Symbol mask,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{data, mask};
  return Symbol::Create("boolean_mask_dense", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_add: positional inputs (a, b)
inline Symbol broadcast_add(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_add", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_axis: positional inputs (x)
inline Symbol broadcast_axis(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& size = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "size", detail::Raw{size});
  std::vector<Symbol> ins{x};
  return Symbol::Create("broadcast_axis", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_div: positional inputs (a, b)
inline Symbol broadcast_div(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_div", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_equal: positional inputs (a, b)
inline Symbol broadcast_equal(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_equal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_greater: positional inputs (a, b)
inline Symbol broadcast_greater(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_greater", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_greater_equal: positional inputs (a, b)
inline Symbol broadcast_greater_equal(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_greater_equal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_hypot: positional inputs (a, b)
inline Symbol broadcast_hypot(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_hypot", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_lesser: positional inputs (a, b)
inline Symbol broadcast_lesser(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_lesser", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_lesser_equal: positional inputs (a, b)
inline Symbol broadcast_lesser_equal(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_lesser_equal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_like: positional inputs (lhs, rhs)
inline Symbol broadcast_like(
    const std::string& name,
    Symbol lhs,
    Symbol rhs,
    const std::string& lhs_axes = "null" /* raw JSON */,
    const std::string& rhs_axes = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lhs_axes", detail::Raw{lhs_axes});
  detail::Attr(&j, "rhs_axes", detail::Raw{rhs_axes});
  std::vector<Symbol> ins{lhs, rhs};
  return Symbol::Create("broadcast_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_logical_and: positional inputs (a, b)
inline Symbol broadcast_logical_and(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_logical_and", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_logical_or: positional inputs (a, b)
inline Symbol broadcast_logical_or(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_logical_or", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_logical_xor: positional inputs (a, b)
inline Symbol broadcast_logical_xor(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_logical_xor", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_maximum: positional inputs (a, b)
inline Symbol broadcast_maximum(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_maximum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_minimum: positional inputs (a, b)
inline Symbol broadcast_minimum(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_minimum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_mod: positional inputs (a, b)
inline Symbol broadcast_mod(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_mod", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_mul: positional inputs (a, b)
inline Symbol broadcast_mul(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_mul", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_not_equal: positional inputs (a, b)
inline Symbol broadcast_not_equal(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_not_equal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_power: positional inputs (a, b)
inline Symbol broadcast_power(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_power", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_sub: positional inputs (a, b)
inline Symbol broadcast_sub(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("broadcast_sub", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// broadcast_to: positional inputs (x)
inline Symbol broadcast_to(
    const std::string& name,
    Symbol x,
    const std::string& shape = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  std::vector<Symbol> ins{x};
  return Symbol::Create("broadcast_to", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cast: positional inputs (x)
inline Symbol cast(
    const std::string& name,
    Symbol x,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x};
  return Symbol::Create("cast", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cbrt: positional inputs (x)
inline Symbol cbrt(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("cbrt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ceil: positional inputs (x)
inline Symbol ceil(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("ceil", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// clip: positional inputs (x)
inline Symbol clip(
    const std::string& name,
    Symbol x,
    const std::string& a_min = "null" /* raw JSON */,
    const std::string& a_max = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "a_min", detail::Raw{a_min});
  detail::Attr(&j, "a_max", detail::Raw{a_max});
  std::vector<Symbol> ins{x};
  return Symbol::Create("clip", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// col2im: positional inputs (data)
inline Symbol col2im(
    const std::string& name,
    Symbol data,
    const std::string& output_size = "null" /* raw JSON */,
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& dilate = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "output_size", detail::Raw{output_size});
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "dilate", detail::Raw{dilate});
  detail::Attr(&j, "pad", detail::Raw{pad});
  std::vector<Symbol> ins{data};
  return Symbol::Create("col2im", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// concat: positional inputs (variadic)
inline Symbol concat(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t dim = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "dim", static_cast<int64_t>(dim));
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("concat", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cos: positional inputs (x)
inline Symbol cos(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("cos", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cosh: positional inputs (x)
inline Symbol cosh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("cosh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cumprod: positional inputs (x)
inline Symbol cumprod(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x};
  return Symbol::Create("cumprod", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// cumsum: positional inputs (x)
inline Symbol cumsum(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x};
  return Symbol::Create("cumsum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// degrees: positional inputs (x)
inline Symbol degrees(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("degrees", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// depth_to_space: positional inputs (x)
inline Symbol depth_to_space(
    const std::string& name,
    Symbol x,
    const std::string& block_size = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "block_size", detail::Raw{block_size});
  std::vector<Symbol> ins{x};
  return Symbol::Create("depth_to_space", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dgl_adjacency: positional inputs (indptr, indices)
inline Symbol dgl_adjacency(
    const std::string& name,
    Symbol indptr,
    Symbol indices,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{indptr, indices};
  return Symbol::Create("dgl_adjacency", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dgl_csr_neighbor_non_uniform_sample: positional inputs (probability, indptr, indices, seeds)
inline Symbol dgl_csr_neighbor_non_uniform_sample(
    const std::string& name,
    Symbol probability,
    Symbol indptr,
    Symbol indices,
    Symbol seeds,
    int64_t num_neighbor = 2,
    int64_t max_num_vertices = 64,
    int64_t seed = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_neighbor", static_cast<int64_t>(num_neighbor));
  detail::Attr(&j, "max_num_vertices", static_cast<int64_t>(max_num_vertices));
  detail::Attr(&j, "seed", static_cast<int64_t>(seed));
  std::vector<Symbol> ins{probability, indptr, indices, seeds};
  return Symbol::Create("dgl_csr_neighbor_non_uniform_sample", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dgl_csr_neighbor_uniform_sample: positional inputs (indptr, indices, seeds)
inline Symbol dgl_csr_neighbor_uniform_sample(
    const std::string& name,
    Symbol indptr,
    Symbol indices,
    Symbol seeds,
    int64_t num_neighbor = 2,
    int64_t max_num_vertices = 64,
    int64_t seed = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_neighbor", static_cast<int64_t>(num_neighbor));
  detail::Attr(&j, "max_num_vertices", static_cast<int64_t>(max_num_vertices));
  detail::Attr(&j, "seed", static_cast<int64_t>(seed));
  std::vector<Symbol> ins{indptr, indices, seeds};
  return Symbol::Create("dgl_csr_neighbor_uniform_sample", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dgl_graph_compact: positional inputs (indptr, indices, data)
inline Symbol dgl_graph_compact(
    const std::string& name,
    Symbol indptr,
    Symbol indices,
    Symbol data,
    const std::string& graph_sizes = "null" /* raw JSON */,
    bool return_mapping = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "graph_sizes", detail::Raw{graph_sizes});
  detail::Attr(&j, "return_mapping", return_mapping);
  std::vector<Symbol> ins{indptr, indices, data};
  return Symbol::Create("dgl_graph_compact", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dgl_subgraph: positional inputs (indptr, indices, data, vids)
inline Symbol dgl_subgraph(
    const std::string& name,
    Symbol indptr,
    Symbol indices,
    Symbol data,
    Symbol vids,
    bool return_mapping = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "return_mapping", return_mapping);
  std::vector<Symbol> ins{indptr, indices, data, vids};
  return Symbol::Create("dgl_subgraph", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// diag: positional inputs (x)
inline Symbol diag(
    const std::string& name,
    Symbol x,
    int64_t k = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "k", static_cast<int64_t>(k));
  std::vector<Symbol> ins{x};
  return Symbol::Create("diag", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// digamma: positional inputs (x)
inline Symbol digamma(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("digamma", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// dot: positional inputs (a, b)
inline Symbol dot(
    const std::string& name,
    Symbol a,
    Symbol b,
    bool transpose_a = false,
    bool transpose_b = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose_a", transpose_a);
  detail::Attr(&j, "transpose_b", transpose_b);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("dot", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// edge_id: positional inputs (indptr, indices, data, u, v)
inline Symbol edge_id(
    const std::string& name,
    Symbol indptr,
    Symbol indices,
    Symbol data,
    Symbol u,
    Symbol v,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{indptr, indices, data, u, v};
  return Symbol::Create("edge_id", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// einsum: positional inputs (variadic)
inline Symbol einsum(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& subscripts = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "subscripts", detail::Raw{subscripts});
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("einsum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// elemwise_add: positional inputs (a, b)
inline Symbol elemwise_add(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("elemwise_add", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// elemwise_div: positional inputs (a, b)
inline Symbol elemwise_div(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("elemwise_div", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// elemwise_mul: positional inputs (a, b)
inline Symbol elemwise_mul(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("elemwise_mul", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// elemwise_sub: positional inputs (a, b)
inline Symbol elemwise_sub(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("elemwise_sub", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// erf: positional inputs (x)
inline Symbol erf(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("erf", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// erf_inv: positional inputs (x)
inline Symbol erf_inv(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("erf_inv", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// erfinv: positional inputs (x)
inline Symbol erfinv(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("erfinv", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// exp: positional inputs (x)
inline Symbol exp(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("exp", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// expand_dims: positional inputs (x)
inline Symbol expand_dims(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{x};
  return Symbol::Create("expand_dims", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// expm1: positional inputs (x)
inline Symbol expm1(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("expm1", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// fix: positional inputs (x)
inline Symbol fix(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("fix", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// flash_attention: positional inputs (q, k, v)
inline Symbol flash_attention(
    const std::string& name,
    Symbol q,
    Symbol k,
    Symbol v,
    bool causal = false,
    const std::string& sm_scale = "null" /* raw JSON */,
    const std::string& block_q = "null" /* raw JSON */,
    const std::string& block_k = "null" /* raw JSON */,
    const std::string& interpret = "null" /* raw JSON */,
    const std::string& window = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "causal", causal);
  detail::Attr(&j, "sm_scale", detail::Raw{sm_scale});
  detail::Attr(&j, "block_q", detail::Raw{block_q});
  detail::Attr(&j, "block_k", detail::Raw{block_k});
  detail::Attr(&j, "interpret", detail::Raw{interpret});
  detail::Attr(&j, "window", detail::Raw{window});
  std::vector<Symbol> ins{q, k, v};
  return Symbol::Create("flash_attention", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// flatten: positional inputs (x)
inline Symbol flatten(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("flatten", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// floor: positional inputs (x)
inline Symbol floor(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("floor", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ftml_update: positional inputs (weight, grad, d, v, z)
inline Symbol ftml_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol d,
    Symbol v,
    Symbol z,
    const std::string& lr = "null" /* raw JSON */,
    double beta1 = 0.6,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    const std::string& t = "null" /* raw JSON */,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_grad = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "t", detail::Raw{t});
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_grad", clip_grad);
  std::vector<Symbol> ins{weight, grad, d, v, z};
  return Symbol::Create("ftml_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ftrl_update: positional inputs (weight, grad, z, n)
inline Symbol ftrl_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol z,
    Symbol n,
    const std::string& lr = "null" /* raw JSON */,
    double lamda1 = 0.01,
    double beta = 1.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "lamda1", lamda1);
  detail::Attr(&j, "beta", beta);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, z, n};
  return Symbol::Create("ftrl_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// gamma: positional inputs (x)
inline Symbol gamma(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("gamma", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// gammaln: positional inputs (x)
inline Symbol gammaln(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("gammaln", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// gather_nd: positional inputs (x, indices)
inline Symbol gather_nd(
    const std::string& name,
    Symbol x,
    Symbol indices,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, indices};
  return Symbol::Create("gather_nd", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// gelu: positional inputs (x)
inline Symbol gelu(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("gelu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// gelu_tanh: positional inputs (x)
inline Symbol gelu_tanh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("gelu_tanh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// group_adagrad_update: positional inputs (weight, grad, history)
inline Symbol group_adagrad_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol history,
    const std::string& lr = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double epsilon = 1e-05,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "epsilon", epsilon);
  std::vector<Symbol> ins{weight, grad, history};
  return Symbol::Create("group_adagrad_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// hard_sigmoid: positional inputs (x)
inline Symbol hard_sigmoid(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("hard_sigmoid", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// histogram: positional inputs (data, bins)
inline Symbol histogram(
    const std::string& name,
    Symbol data,
    Symbol bins = Symbol(),
    const std::string& bin_cnt = "null" /* raw JSON */,
    const std::string& range = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "bin_cnt", detail::Raw{bin_cnt});
  detail::Attr(&j, "range", detail::Raw{range});
  std::vector<Symbol> ins{data, bins};
  return Symbol::Create("histogram", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// hypot: positional inputs (a, b)
inline Symbol hypot(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("hypot", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// identity: positional inputs (x)
inline Symbol identity(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("identity", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// im2col: positional inputs (data)
inline Symbol im2col(
    const std::string& name,
    Symbol data,
    const std::string& kernel = "null" /* raw JSON */,
    const std::string& stride = "null" /* raw JSON */,
    const std::string& dilate = "null" /* raw JSON */,
    const std::string& pad = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "kernel", detail::Raw{kernel});
  detail::Attr(&j, "stride", detail::Raw{stride});
  detail::Attr(&j, "dilate", detail::Raw{dilate});
  detail::Attr(&j, "pad", detail::Raw{pad});
  std::vector<Symbol> ins{data};
  return Symbol::Create("im2col", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// index_add: positional inputs (lhs, data, indices)
inline Symbol index_add(
    const std::string& name,
    Symbol lhs,
    Symbol data,
    Symbol indices,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{lhs, data, indices};
  return Symbol::Create("index_add", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// index_copy: positional inputs (old, idx, new)
inline Symbol index_copy(
    const std::string& name,
    Symbol old,
    Symbol idx,
    Symbol new_,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{old, idx, new_};
  return Symbol::Create("index_copy", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// isfinite: positional inputs (x)
inline Symbol isfinite(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("isfinite", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// isinf: positional inputs (x)
inline Symbol isinf(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("isinf", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// isnan: positional inputs (x)
inline Symbol isnan(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("isnan", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// khatri_rao: positional inputs (variadic)
inline Symbol khatri_rao(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("khatri_rao", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// lamb_update_phase1: positional inputs (weight, grad, mean, var)
inline Symbol lamb_update_phase1(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    const std::string& t = "null" /* raw JSON */,
    bool bias_correction = true,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "t", detail::Raw{t});
  detail::Attr(&j, "bias_correction", bias_correction);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mean, var};
  return Symbol::Create("lamb_update_phase1", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// lamb_update_phase2: positional inputs (weight, g, r1, r2)
inline Symbol lamb_update_phase2(
    const std::string& name,
    Symbol weight,
    Symbol g,
    Symbol r1,
    Symbol r2,
    const std::string& lr = "null" /* raw JSON */,
    double lower_bound = -1.0,
    double upper_bound = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "lower_bound", lower_bound);
  detail::Attr(&j, "upper_bound", upper_bound);
  std::vector<Symbol> ins{weight, g, r1, r2};
  return Symbol::Create("lamb_update_phase2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// lars_update: positional inputs (weight, grad, mom)
inline Symbol lars_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    const std::string& lr = "null" /* raw JSON */,
    double eta = 0.001,
    double momentum = 0.9,
    double wd = 0.0,
    double epsilon = 1e-09,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "eta", eta);
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mom};
  return Symbol::Create("lars_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ldexp: positional inputs (a, b)
inline Symbol ldexp(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("ldexp", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// leaky_relu: positional inputs (x)
inline Symbol leaky_relu(
    const std::string& name,
    Symbol x,
    const std::string& act_type = "leaky",
    double slope = 0.25,
    double lower_bound = 0.125,
    double upper_bound = 0.334,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "act_type", act_type);
  detail::Attr(&j, "slope", slope);
  detail::Attr(&j, "lower_bound", lower_bound);
  detail::Attr(&j, "upper_bound", upper_bound);
  std::vector<Symbol> ins{x};
  return Symbol::Create("leaky_relu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_det: positional inputs (a)
inline Symbol linalg_det(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_det", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_extractdiag: positional inputs (a)
inline Symbol linalg_extractdiag(
    const std::string& name,
    Symbol a,
    int64_t offset = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "offset", static_cast<int64_t>(offset));
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_extractdiag", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_extracttrian: positional inputs (a)
inline Symbol linalg_extracttrian(
    const std::string& name,
    Symbol a,
    int64_t offset = 0,
    bool lower = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "offset", static_cast<int64_t>(offset));
  detail::Attr(&j, "lower", lower);
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_extracttrian", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_gelqf: positional inputs (a)
inline Symbol linalg_gelqf(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_gelqf", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_gemm: positional inputs (a, b, c)
inline Symbol linalg_gemm(
    const std::string& name,
    Symbol a,
    Symbol b,
    Symbol c,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose_a", transpose_a);
  detail::Attr(&j, "transpose_b", transpose_b);
  detail::Attr(&j, "alpha", alpha);
  detail::Attr(&j, "beta", beta);
  std::vector<Symbol> ins{a, b, c};
  return Symbol::Create("linalg_gemm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_gemm2: positional inputs (a, b)
inline Symbol linalg_gemm2(
    const std::string& name,
    Symbol a,
    Symbol b,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose_a", transpose_a);
  detail::Attr(&j, "transpose_b", transpose_b);
  detail::Attr(&j, "alpha", alpha);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("linalg_gemm2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_inverse: positional inputs (a)
inline Symbol linalg_inverse(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_inverse", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_makediag: positional inputs (a)
inline Symbol linalg_makediag(
    const std::string& name,
    Symbol a,
    int64_t offset = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "offset", static_cast<int64_t>(offset));
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_makediag", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_maketrian: positional inputs (a)
inline Symbol linalg_maketrian(
    const std::string& name,
    Symbol a,
    int64_t offset = 0,
    bool lower = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "offset", static_cast<int64_t>(offset));
  detail::Attr(&j, "lower", lower);
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_maketrian", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_potrf: positional inputs (a)
inline Symbol linalg_potrf(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_potrf", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_potri: positional inputs (a)
inline Symbol linalg_potri(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_potri", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_slogdet: positional inputs (a)
inline Symbol linalg_slogdet(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_slogdet", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_sumlogdiag: positional inputs (a)
inline Symbol linalg_sumlogdiag(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_sumlogdiag", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_svd: positional inputs (a)
inline Symbol linalg_svd(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_svd", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_syevd: positional inputs (a)
inline Symbol linalg_syevd(
    const std::string& name,
    Symbol a,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_syevd", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_syrk: positional inputs (a)
inline Symbol linalg_syrk(
    const std::string& name,
    Symbol a,
    bool transpose = false,
    double alpha = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose", transpose);
  detail::Attr(&j, "alpha", alpha);
  std::vector<Symbol> ins{a};
  return Symbol::Create("linalg_syrk", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_trmm: positional inputs (a, b)
inline Symbol linalg_trmm(
    const std::string& name,
    Symbol a,
    Symbol b,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose", transpose);
  detail::Attr(&j, "rightside", rightside);
  detail::Attr(&j, "lower", lower);
  detail::Attr(&j, "alpha", alpha);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("linalg_trmm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// linalg_trsm: positional inputs (a, b)
inline Symbol linalg_trsm(
    const std::string& name,
    Symbol a,
    Symbol b,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "transpose", transpose);
  detail::Attr(&j, "rightside", rightside);
  detail::Attr(&j, "lower", lower);
  detail::Attr(&j, "alpha", alpha);
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("linalg_trsm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// log: positional inputs (x)
inline Symbol log(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("log", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// log10: positional inputs (x)
inline Symbol log10(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("log10", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// log1p: positional inputs (x)
inline Symbol log1p(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("log1p", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// log2: positional inputs (x)
inline Symbol log2(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("log2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// log_softmax: positional inputs (x, length)
inline Symbol log_softmax(
    const std::string& name,
    Symbol x,
    Symbol length = Symbol(),
    int64_t axis = -1,
    const std::string& temperature = "null" /* raw JSON */,
    bool use_length = false,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "temperature", detail::Raw{temperature});
  detail::Attr(&j, "use_length", use_length);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x, length};
  return Symbol::Create("log_softmax", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// logical_not: positional inputs (x)
inline Symbol logical_not(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("logical_not", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// logsumexp: positional inputs (x)
inline Symbol logsumexp(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  std::vector<Symbol> ins{x};
  return Symbol::Create("logsumexp", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// make_loss: positional inputs (data)
inline Symbol make_loss(
    const std::string& name,
    Symbol data,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data};
  return Symbol::Create("make_loss", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// masked_softmax: positional inputs (x, mask)
inline Symbol masked_softmax(
    const std::string& name,
    Symbol x,
    Symbol mask,
    int64_t axis = -1,
    double temperature = 1.0,
    bool normalize = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "temperature", temperature);
  detail::Attr(&j, "normalize", normalize);
  std::vector<Symbol> ins{x, mask};
  return Symbol::Create("masked_softmax", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// matmul: positional inputs (a, b)
inline Symbol matmul(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("matmul", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// max: positional inputs (x)
inline Symbol max(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("max", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// maximum: positional inputs (a, b)
inline Symbol maximum(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("maximum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mean: positional inputs (x)
inline Symbol mean(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("mean", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// min: positional inputs (x)
inline Symbol min(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("min", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// minimum: positional inputs (a, b)
inline Symbol minimum(
    const std::string& name,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{a, b};
  return Symbol::Create("minimum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mish: positional inputs (x)
inline Symbol mish(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("mish", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// moe_ffn: positional inputs (x, router, w_gate, w_up, w_down, router_bias)
inline Symbol moe_ffn(
    const std::string& name,
    Symbol x,
    Symbol router,
    Symbol w_gate,
    Symbol w_up,
    Symbol w_down,
    Symbol router_bias = Symbol(),
    const std::string& top_k = "null" /* raw JSON */,
    bool norm_topk = true,
    int64_t first_expert = 0,
    const std::string& n_group = "null" /* raw JSON */,
    const std::string& topk_group = "null" /* raw JSON */,
    double routed_scale = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "top_k", detail::Raw{top_k});
  detail::Attr(&j, "norm_topk", norm_topk);
  detail::Attr(&j, "first_expert", static_cast<int64_t>(first_expert));
  detail::Attr(&j, "n_group", detail::Raw{n_group});
  detail::Attr(&j, "topk_group", detail::Raw{topk_group});
  detail::Attr(&j, "routed_scale", routed_scale);
  std::vector<Symbol> ins{x, router, w_gate, w_up, w_down, router_bias};
  return Symbol::Create("moe_ffn", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// moments: positional inputs (x)
inline Symbol moments(
    const std::string& name,
    Symbol x,
    const std::string& axes = "null" /* raw JSON */,
    bool keepdims = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axes", detail::Raw{axes});
  detail::Attr(&j, "keepdims", keepdims);
  std::vector<Symbol> ins{x};
  return Symbol::Create("moments", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_adamw_update: positional inputs (weight, grad, mean, var, weight32)
inline Symbol mp_adamw_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    Symbol weight32,
    const std::string& lr = "null" /* raw JSON */,
    double eta = 1.0,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "eta", eta);
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mean, var, weight32};
  return Symbol::Create("mp_adamw_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_lamb_update_phase1: positional inputs (weight, grad, mean, var, weight32)
inline Symbol mp_lamb_update_phase1(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    Symbol weight32,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    const std::string& t = "null" /* raw JSON */,
    bool bias_correction = true,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "t", detail::Raw{t});
  detail::Attr(&j, "bias_correction", bias_correction);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mean, var, weight32};
  return Symbol::Create("mp_lamb_update_phase1", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_lamb_update_phase2: positional inputs (weight, g, r1, r2, weight32)
inline Symbol mp_lamb_update_phase2(
    const std::string& name,
    Symbol weight,
    Symbol g,
    Symbol r1,
    Symbol r2,
    Symbol weight32,
    const std::string& lr = "null" /* raw JSON */,
    double lower_bound = -1.0,
    double upper_bound = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "lower_bound", lower_bound);
  detail::Attr(&j, "upper_bound", upper_bound);
  std::vector<Symbol> ins{weight, g, r1, r2, weight32};
  return Symbol::Create("mp_lamb_update_phase2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_nag_mom_update: positional inputs (weight, grad, mom, weight32)
inline Symbol mp_nag_mom_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol weight32,
    const std::string& lr = "null" /* raw JSON */,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mom, weight32};
  return Symbol::Create("mp_nag_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_sgd_mom_update: positional inputs (weight, grad, mom, weight32)
inline Symbol mp_sgd_mom_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol weight32,
    const std::string& lr = "null" /* raw JSON */,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "lazy_update", lazy_update);
  std::vector<Symbol> ins{weight, grad, mom, weight32};
  return Symbol::Create("mp_sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// mp_sgd_update: positional inputs (weight, grad, weight32)
inline Symbol mp_sgd_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol weight32,
    const std::string& lr = "null" /* raw JSON */,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "lazy_update", lazy_update);
  std::vector<Symbol> ins{weight, grad, weight32};
  return Symbol::Create("mp_sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_adamw_update: positional inputs (variadic)
inline Symbol multi_adamw_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& etas = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "etas", detail::Raw{etas});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_adamw_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_all_finite: positional inputs (variadic)
inline Symbol multi_all_finite(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t num_arrays = 0,
    bool init_output = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_arrays", static_cast<int64_t>(num_arrays));
  detail::Attr(&j, "init_output", init_output);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_all_finite", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_head_attention: positional inputs (q, k, v, mask)
inline Symbol multi_head_attention(
    const std::string& name,
    Symbol q,
    Symbol k,
    Symbol v,
    Symbol mask = Symbol(),
    int64_t heads = 1,
    double dropout = 0.0,
    bool causal = false,
    const std::string& use_flash = "null" /* raw JSON */,
    const std::string& sm_scale = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", static_cast<int64_t>(heads));
  detail::Attr(&j, "dropout", dropout);
  detail::Attr(&j, "causal", causal);
  detail::Attr(&j, "use_flash", detail::Raw{use_flash});
  detail::Attr(&j, "sm_scale", detail::Raw{sm_scale});
  std::vector<Symbol> ins{q, k, v, mask};
  return Symbol::Create("multi_head_attention", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_lamb_update: positional inputs (variadic)
inline Symbol multi_lamb_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    const std::string& step_count = "null" /* raw JSON */,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    bool bias_correction = true,
    double lower_bound = -1.0,
    double upper_bound = -1.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "step_count", detail::Raw{step_count});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "bias_correction", bias_correction);
  detail::Attr(&j, "lower_bound", lower_bound);
  detail::Attr(&j, "upper_bound", upper_bound);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_lamb_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_lars: positional inputs (lrs, weights_sum_sq, grads_sum_sq, wds)
inline Symbol multi_lars(
    const std::string& name,
    Symbol lrs,
    Symbol weights_sum_sq,
    Symbol grads_sum_sq,
    Symbol wds,
    const std::string& eta = "null" /* raw JSON */,
    const std::string& eps = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "eta", detail::Raw{eta});
  detail::Attr(&j, "eps", detail::Raw{eps});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  std::vector<Symbol> ins{lrs, weights_sum_sq, grads_sum_sq, wds};
  return Symbol::Create("multi_lars", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_mp_adamw_update: positional inputs (variadic)
inline Symbol multi_mp_adamw_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& etas = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "etas", detail::Raw{etas});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_mp_adamw_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_mp_lamb_update: positional inputs (variadic)
inline Symbol multi_mp_lamb_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    const std::string& step_count = "null" /* raw JSON */,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    bool bias_correction = true,
    double lower_bound = -1.0,
    double upper_bound = -1.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "step_count", detail::Raw{step_count});
  detail::Attr(&j, "beta1", beta1);
  detail::Attr(&j, "beta2", beta2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "bias_correction", bias_correction);
  detail::Attr(&j, "lower_bound", lower_bound);
  detail::Attr(&j, "upper_bound", upper_bound);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_mp_lamb_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_mp_sgd_mom_update: positional inputs (variadic)
inline Symbol multi_mp_sgd_mom_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double momentum = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_mp_sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_mp_sgd_update: positional inputs (variadic)
inline Symbol multi_mp_sgd_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_mp_sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_sgd_mom_update: positional inputs (variadic)
inline Symbol multi_sgd_mom_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double momentum = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_sgd_update: positional inputs (variadic)
inline Symbol multi_sgd_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& lrs = "null" /* raw JSON */,
    const std::string& wds = "null" /* raw JSON */,
    const std::string& num_weights = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lrs", detail::Raw{lrs});
  detail::Attr(&j, "wds", detail::Raw{wds});
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// multi_sum_sq: positional inputs (variadic)
inline Symbol multi_sum_sq(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t num_arrays = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_arrays", static_cast<int64_t>(num_arrays));
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("multi_sum_sq", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// nag_mom_update: positional inputs (weight, grad, mom)
inline Symbol nag_mom_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    const std::string& lr = "null" /* raw JSON */,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad, mom};
  return Symbol::Create("nag_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// nanprod: positional inputs (x)
inline Symbol nanprod(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("nanprod", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// nansum: positional inputs (x)
inline Symbol nansum(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("nansum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// negative: positional inputs (x)
inline Symbol negative(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("negative", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// norm: positional inputs (x)
inline Symbol norm(
    const std::string& name,
    Symbol x,
    int64_t ord = 2,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "ord", static_cast<int64_t>(ord));
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  std::vector<Symbol> ins{x};
  return Symbol::Create("norm", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// one_hot: positional inputs (indices)
inline Symbol one_hot(
    const std::string& name,
    Symbol indices,
    const std::string& depth = "null" /* raw JSON */,
    double on_value = 1.0,
    double off_value = 0.0,
    const std::string& dtype = "float32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "depth", detail::Raw{depth});
  detail::Attr(&j, "on_value", on_value);
  detail::Attr(&j, "off_value", off_value);
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{indices};
  return Symbol::Create("one_hot", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ones_like: positional inputs (x)
inline Symbol ones_like(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("ones_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// pad: positional inputs (x)
inline Symbol pad(
    const std::string& name,
    Symbol x,
    const std::string& mode = "constant",
    const std::string& pad_width = "null" /* raw JSON */,
    double constant_value = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "mode", mode);
  detail::Attr(&j, "pad_width", detail::Raw{pad_width});
  detail::Attr(&j, "constant_value", constant_value);
  std::vector<Symbol> ins{x};
  return Symbol::Create("pad", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// paged_attention: positional inputs (q, k_pool, v_pool, tables, lengths, layer, starts)
inline Symbol paged_attention(
    const std::string& name,
    Symbol q,
    Symbol k_pool,
    Symbol v_pool,
    Symbol tables,
    Symbol lengths,
    Symbol layer,
    Symbol starts = Symbol(),
    const std::string& heads = "null" /* raw JSON */,
    const std::string& kv_heads = "null" /* raw JSON */,
    const std::string& sm_scale = "null" /* raw JSON */,
    const std::string& v_dim = "null" /* raw JSON */,
    const std::string& interpret = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "heads", detail::Raw{heads});
  detail::Attr(&j, "kv_heads", detail::Raw{kv_heads});
  detail::Attr(&j, "sm_scale", detail::Raw{sm_scale});
  detail::Attr(&j, "v_dim", detail::Raw{v_dim});
  detail::Attr(&j, "interpret", detail::Raw{interpret});
  std::vector<Symbol> ins{q, k_pool, v_pool, tables, lengths, layer, starts};
  return Symbol::Create("paged_attention", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// pick: positional inputs (x, indices)
inline Symbol pick(
    const std::string& name,
    Symbol x,
    Symbol indices,
    int64_t axis = -1,
    bool keepdims = false,
    const std::string& mode = "clip",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "mode", mode);
  std::vector<Symbol> ins{x, indices};
  return Symbol::Create("pick", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// preloaded_multi_mp_sgd_mom_update: positional inputs (variadic)
inline Symbol preloaded_multi_mp_sgd_mom_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& num_weights = "null" /* raw JSON */,
    double momentum = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("preloaded_multi_mp_sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// preloaded_multi_mp_sgd_update: positional inputs (variadic)
inline Symbol preloaded_multi_mp_sgd_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& num_weights = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("preloaded_multi_mp_sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// preloaded_multi_sgd_mom_update: positional inputs (variadic)
inline Symbol preloaded_multi_sgd_mom_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& num_weights = "null" /* raw JSON */,
    double momentum = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("preloaded_multi_sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// preloaded_multi_sgd_update: positional inputs (variadic)
inline Symbol preloaded_multi_sgd_update(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    const std::string& num_weights = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_weights", detail::Raw{num_weights});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("preloaded_multi_sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// prelu: positional inputs (x, gamma)
inline Symbol prelu(
    const std::string& name,
    Symbol x,
    Symbol gamma,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x, gamma};
  return Symbol::Create("prelu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// prod: positional inputs (x)
inline Symbol prod(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("prod", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// radians: positional inputs (x)
inline Symbol radians(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("radians", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// ravel_multi_index: positional inputs (data)
inline Symbol ravel_multi_index(
    const std::string& name,
    Symbol data,
    const std::string& shape = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  std::vector<Symbol> ins{data};
  return Symbol::Create("ravel_multi_index", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rcbrt: positional inputs (x)
inline Symbol rcbrt(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("rcbrt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// reciprocal: positional inputs (x)
inline Symbol reciprocal(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("reciprocal", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// relu: positional inputs (x)
inline Symbol relu(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("relu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// repeat: positional inputs (x)
inline Symbol repeat(
    const std::string& name,
    Symbol x,
    const std::string& repeats = "null" /* raw JSON */,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "repeats", detail::Raw{repeats});
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{x};
  return Symbol::Create("repeat", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// reset_arrays: positional inputs (variadic)
inline Symbol reset_arrays(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t num_arrays = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_arrays", static_cast<int64_t>(num_arrays));
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("reset_arrays", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// reshape: positional inputs (x)
inline Symbol reshape(
    const std::string& name,
    Symbol x,
    const std::string& shape = "null" /* raw JSON */,
    bool reverse = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  detail::Attr(&j, "reverse", reverse);
  std::vector<Symbol> ins{x};
  return Symbol::Create("reshape", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// reshape_like: positional inputs (lhs, rhs)
inline Symbol reshape_like(
    const std::string& name,
    Symbol lhs,
    Symbol rhs,
    const std::string& lhs_begin = "null" /* raw JSON */,
    const std::string& lhs_end = "null" /* raw JSON */,
    const std::string& rhs_begin = "null" /* raw JSON */,
    const std::string& rhs_end = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lhs_begin", detail::Raw{lhs_begin});
  detail::Attr(&j, "lhs_end", detail::Raw{lhs_end});
  detail::Attr(&j, "rhs_begin", detail::Raw{rhs_begin});
  detail::Attr(&j, "rhs_end", detail::Raw{rhs_end});
  std::vector<Symbol> ins{lhs, rhs};
  return Symbol::Create("reshape_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// reverse: positional inputs (x)
inline Symbol reverse(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{x};
  return Symbol::Create("reverse", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rint: positional inputs (x)
inline Symbol rint(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("rint", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rmsprop_update: positional inputs (weight, grad, n)
inline Symbol rmsprop_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    const std::string& lr = "null" /* raw JSON */,
    double gamma1 = 0.95,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "gamma1", gamma1);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "clip_weights", clip_weights);
  std::vector<Symbol> ins{weight, grad, n};
  return Symbol::Create("rmsprop_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rmspropalex_update: positional inputs (weight, grad, n, g_avg, delta)
inline Symbol rmspropalex_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    Symbol g_avg,
    Symbol delta,
    const std::string& lr = "null" /* raw JSON */,
    double gamma1 = 0.95,
    double gamma2 = 0.9,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "gamma1", gamma1);
  detail::Attr(&j, "gamma2", gamma2);
  detail::Attr(&j, "epsilon", epsilon);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "clip_weights", clip_weights);
  std::vector<Symbol> ins{weight, grad, n, g_avg, delta};
  return Symbol::Create("rmspropalex_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rotary_embedding: positional inputs (x, positions)
inline Symbol rotary_embedding(
    const std::string& name,
    Symbol x,
    Symbol positions,
    double theta = 10000.0,
    const std::string& scaling = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "theta", theta);
  detail::Attr(&j, "scaling", detail::Raw{scaling});
  std::vector<Symbol> ins{x, positions};
  return Symbol::Create("rotary_embedding", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// round: positional inputs (x)
inline Symbol round(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("round", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// rsqrt: positional inputs (x)
inline Symbol rsqrt(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("rsqrt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// scatter_nd: positional inputs (data, indices)
inline Symbol scatter_nd(
    const std::string& name,
    Symbol data,
    Symbol indices,
    const std::string& shape = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  std::vector<Symbol> ins{data, indices};
  return Symbol::Create("scatter_nd", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sequence_last: positional inputs (data, sequence_length)
inline Symbol sequence_last(
    const std::string& name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    bool use_sequence_length = false,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "use_sequence_length", use_sequence_length);
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{data, sequence_length};
  return Symbol::Create("sequence_last", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sequence_mask: positional inputs (data, sequence_length)
inline Symbol sequence_mask(
    const std::string& name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    bool use_sequence_length = false,
    double value = 0.0,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "use_sequence_length", use_sequence_length);
  detail::Attr(&j, "value", value);
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{data, sequence_length};
  return Symbol::Create("sequence_mask", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sequence_reverse: positional inputs (data, sequence_length)
inline Symbol sequence_reverse(
    const std::string& name,
    Symbol data,
    Symbol sequence_length = Symbol(),
    bool use_sequence_length = false,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "use_sequence_length", use_sequence_length);
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{data, sequence_length};
  return Symbol::Create("sequence_reverse", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sgd_mom_update: positional inputs (weight, grad, mom)
inline Symbol sgd_mom_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    const std::string& lr = "null" /* raw JSON */,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "lazy_update", lazy_update);
  std::vector<Symbol> ins{weight, grad, mom};
  return Symbol::Create("sgd_mom_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sgd_update: positional inputs (weight, grad)
inline Symbol sgd_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    const std::string& lr = "null" /* raw JSON */,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "lazy_update", lazy_update);
  std::vector<Symbol> ins{weight, grad};
  return Symbol::Create("sgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// shape_array: positional inputs (x)
inline Symbol shape_array(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("shape_array", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sigmoid: positional inputs (x)
inline Symbol sigmoid(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("sigmoid", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sign: positional inputs (x)
inline Symbol sign(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("sign", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// signsgd_update: positional inputs (weight, grad)
inline Symbol signsgd_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    const std::string& lr = "null" /* raw JSON */,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  std::vector<Symbol> ins{weight, grad};
  return Symbol::Create("signsgd_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// signum_update: positional inputs (weight, grad, mom)
inline Symbol signum_update(
    const std::string& name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    const std::string& lr = "null" /* raw JSON */,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double wd_lh = 0.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "momentum", momentum);
  detail::Attr(&j, "wd", wd);
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "wd_lh", wd_lh);
  std::vector<Symbol> ins{weight, grad, mom};
  return Symbol::Create("signum_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// silu: positional inputs (x)
inline Symbol silu(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("silu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sin: positional inputs (x)
inline Symbol sin(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("sin", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sinh: positional inputs (x)
inline Symbol sinh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("sinh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// size_array: positional inputs (x)
inline Symbol size_array(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("size_array", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// slice: positional inputs (x)
inline Symbol slice(
    const std::string& name,
    Symbol x,
    const std::string& begin = "null" /* raw JSON */,
    const std::string& end = "null" /* raw JSON */,
    const std::string& step = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "begin", detail::Raw{begin});
  detail::Attr(&j, "end", detail::Raw{end});
  detail::Attr(&j, "step", detail::Raw{step});
  std::vector<Symbol> ins{x};
  return Symbol::Create("slice", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// slice_assign: positional inputs (lhs, rhs)
inline Symbol slice_assign(
    const std::string& name,
    Symbol lhs,
    Symbol rhs,
    const std::string& begin = "null" /* raw JSON */,
    const std::string& end = "null" /* raw JSON */,
    const std::string& step = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "begin", detail::Raw{begin});
  detail::Attr(&j, "end", detail::Raw{end});
  detail::Attr(&j, "step", detail::Raw{step});
  std::vector<Symbol> ins{lhs, rhs};
  return Symbol::Create("slice_assign", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// slice_assign_scalar: positional inputs (lhs)
inline Symbol slice_assign_scalar(
    const std::string& name,
    Symbol lhs,
    const std::string& scalar = "null" /* raw JSON */,
    const std::string& begin = "null" /* raw JSON */,
    const std::string& end = "null" /* raw JSON */,
    const std::string& step = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", detail::Raw{scalar});
  detail::Attr(&j, "begin", detail::Raw{begin});
  detail::Attr(&j, "end", detail::Raw{end});
  detail::Attr(&j, "step", detail::Raw{step});
  std::vector<Symbol> ins{lhs};
  return Symbol::Create("slice_assign_scalar", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// slice_axis: positional inputs (x)
inline Symbol slice_axis(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& begin = "null" /* raw JSON */,
    const std::string& end = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "begin", detail::Raw{begin});
  detail::Attr(&j, "end", detail::Raw{end});
  std::vector<Symbol> ins{x};
  return Symbol::Create("slice_axis", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// slice_like: positional inputs (x, shape_like)
inline Symbol slice_like(
    const std::string& name,
    Symbol x,
    Symbol shape_like,
    const std::string& axes = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axes", detail::Raw{axes});
  std::vector<Symbol> ins{x, shape_like};
  return Symbol::Create("slice_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// smooth_l1: positional inputs (x)
inline Symbol smooth_l1(
    const std::string& name,
    Symbol x,
    double scalar = 1.0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "scalar", scalar);
  std::vector<Symbol> ins{x};
  return Symbol::Create("smooth_l1", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// softmax: positional inputs (x, length)
inline Symbol softmax(
    const std::string& name,
    Symbol x,
    Symbol length = Symbol(),
    int64_t axis = -1,
    const std::string& temperature = "null" /* raw JSON */,
    bool use_length = false,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "temperature", detail::Raw{temperature});
  detail::Attr(&j, "use_length", use_length);
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x, length};
  return Symbol::Create("softmax", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// softmax_cross_entropy: positional inputs (data, label)
inline Symbol softmax_cross_entropy(
    const std::string& name,
    Symbol data,
    Symbol label,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{data, label};
  return Symbol::Create("softmax_cross_entropy", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// softmin: positional inputs (x)
inline Symbol softmin(
    const std::string& name,
    Symbol x,
    int64_t axis = -1,
    const std::string& temperature = "null" /* raw JSON */,
    const std::string& dtype = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "temperature", detail::Raw{temperature});
  detail::Attr(&j, "dtype", detail::Raw{dtype});
  std::vector<Symbol> ins{x};
  return Symbol::Create("softmin", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// softrelu: positional inputs (x)
inline Symbol softrelu(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("softrelu", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// softsign: positional inputs (x)
inline Symbol softsign(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("softsign", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sort: positional inputs (x)
inline Symbol sort(
    const std::string& name,
    Symbol x,
    int64_t axis = -1,
    bool is_ascend = true,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "is_ascend", is_ascend);
  std::vector<Symbol> ins{x};
  return Symbol::Create("sort", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// space_to_depth: positional inputs (x)
inline Symbol space_to_depth(
    const std::string& name,
    Symbol x,
    const std::string& block_size = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "block_size", detail::Raw{block_size});
  std::vector<Symbol> ins{x};
  return Symbol::Create("space_to_depth", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sparse_adagrad_update: positional inputs (weight, grad_values, grad_indices, history)
inline Symbol sparse_adagrad_update(
    const std::string& name,
    Symbol weight,
    Symbol grad_values,
    Symbol grad_indices,
    Symbol history,
    const std::string& lr = "null" /* raw JSON */,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double epsilon = 1e-07,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "lr", detail::Raw{lr});
  detail::Attr(&j, "rescale_grad", rescale_grad);
  detail::Attr(&j, "clip_gradient", clip_gradient);
  detail::Attr(&j, "epsilon", epsilon);
  std::vector<Symbol> ins{weight, grad_values, grad_indices, history};
  return Symbol::Create("sparse_adagrad_update", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// split: positional inputs (x)
inline Symbol split(
    const std::string& name,
    Symbol x,
    const std::string& num_outputs = "null" /* raw JSON */,
    int64_t axis = 1,
    bool squeeze_axis = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "num_outputs", detail::Raw{num_outputs});
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "squeeze_axis", squeeze_axis);
  std::vector<Symbol> ins{x};
  return Symbol::Create("split", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// split_v2: positional inputs (x)
inline Symbol split_v2(
    const std::string& name,
    Symbol x,
    const std::string& indices_or_sections = "null" /* raw JSON */,
    int64_t axis = 0,
    bool squeeze_axis = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "indices_or_sections", detail::Raw{indices_or_sections});
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "squeeze_axis", squeeze_axis);
  std::vector<Symbol> ins{x};
  return Symbol::Create("split_v2", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sqrt: positional inputs (x)
inline Symbol sqrt(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("sqrt", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// square: positional inputs (x)
inline Symbol square(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("square", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// squeeze: positional inputs (x)
inline Symbol squeeze(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  std::vector<Symbol> ins{x};
  return Symbol::Create("squeeze", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// stack: positional inputs (variadic)
inline Symbol stack(
    const std::string& name,
    const std::vector<Symbol>& varargs,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins(varargs);
  return Symbol::Create("stack", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sum: positional inputs (x)
inline Symbol sum(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("sum", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// sum_axis: positional inputs (x)
inline Symbol sum_axis(
    const std::string& name,
    Symbol x,
    const std::string& axis = "null" /* raw JSON */,
    bool keepdims = false,
    bool exclude = false,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", detail::Raw{axis});
  detail::Attr(&j, "keepdims", keepdims);
  detail::Attr(&j, "exclude", exclude);
  std::vector<Symbol> ins{x};
  return Symbol::Create("sum_axis", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// swapaxes: positional inputs (x)
inline Symbol swapaxes(
    const std::string& name,
    Symbol x,
    int64_t dim1 = 0,
    int64_t dim2 = 1,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "dim1", static_cast<int64_t>(dim1));
  detail::Attr(&j, "dim2", static_cast<int64_t>(dim2));
  std::vector<Symbol> ins{x};
  return Symbol::Create("swapaxes", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// take: positional inputs (x, indices)
inline Symbol take(
    const std::string& name,
    Symbol x,
    Symbol indices,
    int64_t axis = 0,
    const std::string& mode = "clip",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "mode", mode);
  std::vector<Symbol> ins{x, indices};
  return Symbol::Create("take", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// take_along_axis: positional inputs (x, indices)
inline Symbol take_along_axis(
    const std::string& name,
    Symbol x,
    Symbol indices,
    int64_t axis = 0,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  std::vector<Symbol> ins{x, indices};
  return Symbol::Create("take_along_axis", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// tan: positional inputs (x)
inline Symbol tan(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("tan", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// tanh: positional inputs (x)
inline Symbol tanh(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("tanh", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// tile: positional inputs (x)
inline Symbol tile(
    const std::string& name,
    Symbol x,
    const std::string& reps = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "reps", detail::Raw{reps});
  std::vector<Symbol> ins{x};
  return Symbol::Create("tile", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// topk: positional inputs (x)
inline Symbol topk(
    const std::string& name,
    Symbol x,
    int64_t axis = -1,
    int64_t k = 1,
    const std::string& ret_typ = "indices",
    bool is_ascend = false,
    const std::string& dtype = "float32",
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axis", static_cast<int64_t>(axis));
  detail::Attr(&j, "k", static_cast<int64_t>(k));
  detail::Attr(&j, "ret_typ", ret_typ);
  detail::Attr(&j, "is_ascend", is_ascend);
  detail::Attr(&j, "dtype", dtype);
  std::vector<Symbol> ins{x};
  return Symbol::Create("topk", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// transpose: positional inputs (x)
inline Symbol transpose(
    const std::string& name,
    Symbol x,
    const std::string& axes = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "axes", detail::Raw{axes});
  std::vector<Symbol> ins{x};
  return Symbol::Create("transpose", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// trunc: positional inputs (x)
inline Symbol trunc(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("trunc", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// unique: positional inputs (x)
inline Symbol unique(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("unique", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// unravel_index: positional inputs (data)
inline Symbol unravel_index(
    const std::string& name,
    Symbol data,
    const std::string& shape = "null" /* raw JSON */,
    const std::string& extra_attrs_json = "") {
  std::string j;
  detail::Attr(&j, "shape", detail::Raw{shape});
  std::vector<Symbol> ins{data};
  return Symbol::Create("unravel_index", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// where: positional inputs (cond, a, b)
inline Symbol where(
    const std::string& name,
    Symbol cond,
    Symbol a,
    Symbol b,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{cond, a, b};
  return Symbol::Create("where", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}

// zeros_like: positional inputs (x)
inline Symbol zeros_like(
    const std::string& name,
    Symbol x,
    const std::string& extra_attrs_json = "") {
  std::string j;
  std::vector<Symbol> ins{x};
  return Symbol::Create("zeros_like", name, detail::Inputs(std::move(ins)),
                        detail::Finish(std::move(j), extra_attrs_json));
}


}  // namespace op
}  // namespace mxnet_tpu_cpp

#endif  // MXNET_TPU_CPP_OP_H_
