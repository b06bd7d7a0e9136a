// Train an MLP classifier from C++ through the training ABI — the reference
// cpp-package/example/mlp.cpp flow (build symbols, simple-bind, SGD loop)
// on this stack. Data: a deterministic synthetic 10-class problem with
// MNIST's geometry (784-d inputs, 10 classes; class-centered gaussians) —
// no dataset download happens in this environment. Exits 0 iff accuracy on
// a held-out split exceeds 95%.
//
// Build/run (see tests/test_cpp_package.py):
//   g++ -std=c++17 train_mlp.cpp -L<native> -lmxtpu_train -o train_mlp
#include <cmath>
#include <map>
#include <cstdio>
#include <random>
#include <string>
#include <vector>

#include "../include/mxnet_tpu_cpp/train.hpp"

using mxnet_tpu_cpp::Executor;
using mxnet_tpu_cpp::Optimizer;
using mxnet_tpu_cpp::Symbol;

namespace {

constexpr int kFeat = 784;
constexpr int kClasses = 10;
constexpr int kBatch = 64;
constexpr int kTrainBatches = 50;
constexpr int kTestBatches = 10;

// deterministic synthetic "MNIST": per-class center + noise, scaled to
// MNIST-normalized magnitudes (~[0, 0.35] per pixel)
void MakeBatch(std::mt19937* rng, std::vector<float>* x,
               std::vector<float>* y) {
  std::normal_distribution<float> noise(0.0f, 0.35f);
  std::uniform_int_distribution<int> cls(0, kClasses - 1);
  x->assign(kBatch * kFeat, 0.0f);
  y->assign(kBatch, 0.0f);
  for (int i = 0; i < kBatch; ++i) {
    int c = cls(*rng);
    (*y)[i] = static_cast<float>(c);
    std::mt19937 center_rng(1234 + c);
    center_rng.discard(800);  // decorrelate nearby seeds before drawing
    std::normal_distribution<float> cdist(0.0f, 1.0f);
    for (int j = 0; j < kFeat; ++j) {
      (*x)[i * kFeat + j] = cdist(center_rng) + noise(*rng);
    }
  }
}

}  // namespace

static int Run() {
  // ---- network: 784 -> 128 relu -> 64 relu -> 10 softmax ----
  Symbol data = Symbol::Variable("data");
  Symbol label = Symbol::Variable("softmax_label");
  Symbol fc1 = Symbol::Create("FullyConnected", "fc1", {data},
                              "{\"num_hidden\": 128}");
  Symbol act1 = Symbol::Create("Activation", "act1", {fc1},
                               "{\"act_type\": \"relu\"}");
  Symbol fc2 = Symbol::Create("FullyConnected", "fc2", {act1},
                              "{\"num_hidden\": 64}");
  Symbol act2 = Symbol::Create("Activation", "act2", {fc2},
                               "{\"act_type\": \"relu\"}");
  Symbol fc3 = Symbol::Create("FullyConnected", "fc3", {act2},
                              "{\"num_hidden\": 10}");
  Symbol net = Symbol::Create("SoftmaxOutput", "softmax", {fc3, label},
                              "{\"normalization\": \"batch\"}");

  Executor exec(net, "{\"data\": [" + std::to_string(kBatch) + ", " +
                         std::to_string(kFeat) + "], \"softmax_label\": [" +
                         std::to_string(kBatch) + "]}");

  // ---- per-layer Xavier init for weights, zero biases ----
  std::mt19937 rng(7);
  auto args = exec.ListArguments();
  const std::map<std::string, int> fan = {
      {"fc1_weight", kFeat + 128}, {"fc2_weight", 128 + 64},
      {"fc3_weight", 64 + kClasses}};
  for (const auto& name : args) {
    if (name == "data" || name == "softmax_label") continue;
    unsigned n = exec.ArgSize(name);
    std::vector<float> w(n, 0.0f);
    auto it = fan.find(name);
    if (it != fan.end()) {
      float scale = std::sqrt(6.0f / it->second);
      std::uniform_real_distribution<float> u(-scale, scale);
      for (auto& v : w) v = u(rng);
    }
    exec.SetArg(name, w);
  }

  Optimizer sgd("sgd", "{\"learning_rate\": 0.1, \"momentum\": 0.9}");

  // ---- training loop (reference mlp.cpp shape: forward/backward/update) ---
  std::vector<float> x, y;
  for (int epoch = 0; epoch < 2; ++epoch) {
    std::mt19937 erng(100 + epoch);
    int correct = 0, seen = 0;
    for (int b = 0; b < kTrainBatches; ++b) {
      MakeBatch(&erng, &x, &y);
      exec.SetArg("data", x);
      exec.SetArg("softmax_label", y);
      exec.Forward(true);
      exec.Backward();
      std::vector<float> probs = exec.GetOutput(0);
      for (int i = 0; i < kBatch; ++i) {
        int best = 0;
        for (int c = 1; c < kClasses; ++c) {
          if (probs[i * kClasses + c] > probs[i * kClasses + best]) best = c;
        }
        correct += (best == static_cast<int>(y[i]));
        ++seen;
      }
      int idx = 0;
      for (const auto& name : args) {
        if (name != "data" && name != "softmax_label") {
          sgd.Update(exec, name, idx);
        }
        ++idx;
      }
    }
    std::printf("epoch %d train accuracy: %.4f\n", epoch,
                static_cast<double>(correct) / seen);
  }

  // ---- evaluation on a held-out split ----
  std::mt19937 test_rng(999);
  int correct = 0, total = 0;
  for (int b = 0; b < kTestBatches; ++b) {
    MakeBatch(&test_rng, &x, &y);
    exec.SetArg("data", x);
    exec.SetArg("softmax_label", y);
    exec.Forward(false);
    std::vector<float> probs = exec.GetOutput(0);
    for (int i = 0; i < kBatch; ++i) {
      int best = 0;
      for (int c = 1; c < kClasses; ++c) {
        if (probs[i * kClasses + c] > probs[i * kClasses + best]) best = c;
      }
      correct += (best == static_cast<int>(y[i]));
      ++total;
    }
  }
  double acc = static_cast<double>(correct) / total;
  std::printf("cpp-train accuracy: %.4f (%d/%d)\n", acc, correct, total);
  return acc > 0.95 ? 0 : 1;
}

int main() {
  int rc = Run();             // every handle is freed when Run returns
  mxnet_tpu_cpp::Shutdown();
  return rc;
}
