// Exercises the GENERATED typed op surface (op.h, from tools/gen_cpp_ops.py;
// parity: the reference's generated cpp-package/include/mxnet-cpp/op.h used
// by every C++ example). Builds a small conv net purely through generated
// functions — fixed/optional/variadic symbol inputs, typed int/bool/double
// attrs, raw-JSON tuple attrs, and the extra_attrs_json escape hatch — then
// simple-binds, runs forward and backward, and checks the results.
#include <cmath>
#include <cstdio>
#include <string>
#include <vector>

#include <mxnet_tpu_cpp/op.h>

using mxnet_tpu_cpp::Executor;
using mxnet_tpu_cpp::Symbol;
namespace op = mxnet_tpu_cpp::op;

static int Run() {
  Symbol data = Symbol::Variable("data");
  Symbol label = Symbol::Variable("softmax_label");

  // conv stack: raw-JSON tuple attrs (kernel/pad), typed int attr
  Symbol w1 = Symbol::Variable("w1");
  Symbol conv = op::Convolution("conv1", data, w1, Symbol(),
                                /*kernel=*/"[3, 3]", /*stride=*/"[1, 1]",
                                /*dilate=*/"null", /*pad=*/"[1, 1]",
                                /*num_filter=*/8, /*num_group=*/1,
                                /*no_bias=*/true);
  Symbol act = op::Activation("relu1", conv, "relu");
  Symbol pool = op::Pooling("pool1", act, /*kernel=*/"[2, 2]",
                            /*pool_type=*/"max", /*global_pool=*/false,
                            /*stride=*/"[2, 2]");
  // two branches through elemwise + variadic concat + leaky_relu
  Symbol b1 = op::leaky_relu("lrelu", pool, "leaky", 0.1);
  Symbol b2 = op::elemwise_mul("emul", pool, pool);
  Symbol sum = op::elemwise_add("eadd", b1, b2);
  Symbol cat = op::concat("cat", {b1, b2, sum}, /*dim=*/1);
  Symbol flat = op::flatten("flat", cat);
  // fully connected through the escape hatch for one attr
  Symbol w2 = Symbol::Variable("w2");
  Symbol b = Symbol::Variable("b");
  // extra_attrs_json escape hatch: duplicate key parses last-wins, so this
  // overrides the typed flatten=false back to true
  Symbol fc = op::FullyConnected("fc1", flat, w2, b, /*num_hidden=*/10,
                                 /*no_bias=*/false, /*flatten=*/false,
                                 "{\"flatten\": true}");
  Symbol out = op::SoftmaxOutput("softmax", fc, label);

  Executor exec(out, "{\"data\": [2, 1, 8, 8], \"softmax_label\": [2]}");

  // deterministic-ish init
  for (const auto& arg : exec.ListArguments()) {
    if (arg == "data" || arg == "softmax_label") continue;
    unsigned n = exec.ArgSize(arg);
    std::vector<float> v(n);
    for (unsigned i = 0; i < n; ++i)
      v[i] = 0.01f * (float)((int)(i % 11) - 5);
    exec.SetArg(arg, v);
  }
  {
    std::vector<float> x(2 * 1 * 8 * 8);
    for (unsigned i = 0; i < x.size(); ++i) x[i] = 0.01f * (float)(i % 17);
    exec.SetArg("data", x);
    exec.SetArg("softmax_label", {1.0f, 3.0f});
  }

  exec.Forward(true);
  std::vector<float> probs = exec.GetOutput(0);
  if (probs.size() != 20) {
    std::fprintf(stderr, "bad output size %zu\n", probs.size());
    return 1;
  }
  float rowsum = 0.f;
  for (unsigned i = 0; i < 10; ++i) rowsum += probs[i];
  if (std::fabs(rowsum - 1.0f) > 1e-3f || std::isnan(rowsum)) {
    std::fprintf(stderr, "softmax row does not sum to 1: %f\n", rowsum);
    return 1;
  }
  exec.Backward();
  std::vector<float> g = exec.GetGrad("w2");
  float gnorm = 0.f;
  for (float v : g) gnorm += v * v;
  if (!(gnorm > 0.f) || std::isnan(gnorm)) {
    std::fprintf(stderr, "w2 grad degenerate: %f\n", gnorm);
    return 1;
  }
  std::printf("cpp-op-surface OK: probs_row0_sum=%f w2_gnorm=%f\n",
              rowsum, gnorm);
  return 0;
}

int main() {
  int rc = Run();             // every handle is freed when Run returns
  mxnet_tpu_cpp::Shutdown();
  return rc;
}
