// C++ training binding (parity: cpp-package/include/mxnet-cpp/ symbol.h,
// executor.h, optimizer.h — the surface the reference's mlp.cpp / lenet.cpp
// training examples use). RAII wrappers over the libmxtpu_train.so C ABI
// (mxnet_tpu/native/c_train_api.h).
#ifndef MXNET_TPU_CPP_TRAIN_HPP_
#define MXNET_TPU_CPP_TRAIN_HPP_

#include <map>
#include <memory>
#include <stdexcept>
#include <string>
#include <utility>
#include <vector>

#include "../../../mxnet_tpu/native/c_train_api.h"

namespace mxnet_tpu_cpp {

class TrainError : public std::runtime_error {
 public:
  explicit TrainError(const std::string& what) : std::runtime_error(what) {}
};

inline void TrCheck(int rc, const char* op) {
  if (rc != 0) {
    throw TrainError(std::string(op) + " failed: " + MXTrGetLastError());
  }
}

// Symbolic expression handle (mxnet-cpp Symbol analog).
class Symbol {
 public:
  Symbol() = default;
  static Symbol Variable(const std::string& name) {
    void* h = nullptr;
    TrCheck(MXTrSymbolVariable(name.c_str(), &h), "SymbolVariable");
    return Symbol(h);
  }
  // Op application: Symbol::Create("FullyConnected", "fc1", {data},
  //                                "{\"num_hidden\": 128}")
  static Symbol Create(const std::string& op, const std::string& name,
                       const std::vector<Symbol>& inputs,
                       const std::string& attrs_json = "") {
    std::vector<void*> ins;
    ins.reserve(inputs.size());
    for (const auto& s : inputs) ins.push_back(s.handle());
    void* h = nullptr;
    TrCheck(MXTrSymbolCreate(op.c_str(), name.c_str(), ins.data(),
                             static_cast<unsigned>(ins.size()),
                             attrs_json.c_str(), &h),
            "SymbolCreate");
    return Symbol(h);
  }
  void* handle() const { return h_.get(); }

 private:
  explicit Symbol(void* h)
      : h_(h, [](void* p) { MXTrSymbolFree(p); }) {}
  std::shared_ptr<void> h_;
};

// Bound trainable executor (mxnet-cpp Executor analog): owns argument,
// gradient and output buffers on the runtime side.
class Executor {
 public:
  // shapes_json: {"data": [batch, ...], "softmax_label": [batch]}
  Executor(const Symbol& sym, const std::string& shapes_json) {
    void* h = nullptr;
    TrCheck(MXTrSimpleBind(sym.handle(), shapes_json.c_str(), &h),
            "SimpleBind");
    h_.reset(h, [](void* p) { MXTrExecutorFree(p); });
  }

  std::vector<std::string> ListArguments() const {
    unsigned n = 0;
    char* blob = nullptr;
    TrCheck(MXTrExecutorListArguments(h_.get(), &n, &blob), "ListArguments");
    std::vector<std::string> out;
    const char* p = blob;
    for (unsigned i = 0; i < n; ++i) {
      out.emplace_back(p);
      p += out.back().size() + 1;
    }
    MXTrBufFree(blob);
    return out;
  }

  unsigned ArgSize(const std::string& name) const {
    unsigned s = 0;
    TrCheck(MXTrExecutorArgSize(h_.get(), name.c_str(), &s), "ArgSize");
    return s;
  }
  unsigned OutputSize(unsigned index = 0) const {
    unsigned s = 0;
    TrCheck(MXTrExecutorOutputSize(h_.get(), index, &s), "OutputSize");
    return s;
  }

  void SetArg(const std::string& name, const std::vector<float>& data) {
    TrCheck(MXTrExecutorSetArg(h_.get(), name.c_str(), data.data(),
                               static_cast<unsigned>(data.size())),
            "SetArg");
  }
  std::vector<float> GetArg(const std::string& name) const {
    std::vector<float> out(ArgSize(name));
    TrCheck(MXTrExecutorGetArg(h_.get(), name.c_str(), out.data(),
                               static_cast<unsigned>(out.size())),
            "GetArg");
    return out;
  }
  std::vector<float> GetGrad(const std::string& name) const {
    std::vector<float> out(ArgSize(name));
    TrCheck(MXTrExecutorGetGrad(h_.get(), name.c_str(), out.data(),
                                static_cast<unsigned>(out.size())),
            "GetGrad");
    return out;
  }
  std::vector<float> GetOutput(unsigned index = 0) const {
    std::vector<float> out(OutputSize(index));
    TrCheck(MXTrExecutorGetOutput(h_.get(), index, out.data(),
                                  static_cast<unsigned>(out.size())),
            "GetOutput");
    return out;
  }

  void Forward(bool is_train) {
    TrCheck(MXTrExecutorForward(h_.get(), is_train ? 1 : 0), "Forward");
  }
  void Backward() { TrCheck(MXTrExecutorBackward(h_.get()), "Backward"); }

  void* handle() const { return h_.get(); }

 private:
  std::shared_ptr<void> h_;
};

// Optimizer over an executor's arguments (mxnet-cpp optimizer.h analog).
class Optimizer {
 public:
  Optimizer(const std::string& type, const std::string& params_json = "") {
    void* h = nullptr;
    TrCheck(MXTrOptimizerCreate(type.c_str(), params_json.c_str(), &h),
            "OptimizerCreate");
    h_.reset(h, [](void* p) { MXTrOptimizerFree(p); });
  }
  // Update one argument in place from its gradient (per-arg states by index)
  void Update(const Executor& exec, const std::string& arg_name, int index) {
    TrCheck(MXTrOptimizerUpdate(h_.get(), exec.handle(), arg_name.c_str(),
                                index),
            "OptimizerUpdate");
  }

 private:
  std::shared_ptr<void> h_;
};

// Call once at the end of main, after every Symbol, Executor and Optimizer
// has gone out of scope (the reference's examples end with
// MXNotifyShutdown()): the embedded runtime stops before exit() runs static
// destructors under its threads.
inline void Shutdown() { MXTrShutdown(); }

}  // namespace mxnet_tpu_cpp

#endif  // MXNET_TPU_CPP_TRAIN_HPP_
