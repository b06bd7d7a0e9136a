// C training ABI (parity: the training slice of include/mxnet/c_api.h the
// reference cpp-package builds on — symbol creation, simple-bind executors,
// forward/backward, per-argument optimizer updates). Implemented by
// native/train.cc (libmxtpu_train.so, embeds CPython and drives
// mxnet_tpu.c_train); consumed by cpp-package/include/mxnet_tpu_cpp/train.hpp.
#ifndef MXTPU_C_TRAIN_API_H_
#define MXTPU_C_TRAIN_API_H_

#ifdef __cplusplus
extern "C" {
#endif

// every call returns 0 on success; on failure MXTrGetLastError() describes it
const char* MXTrGetLastError();

// -- symbols ----------------------------------------------------------------
int MXTrSymbolVariable(const char* name, void** out);
// op_name: registered op (e.g. "FullyConnected"); attrs_json: keyword
// attributes as a JSON object ("" for none); inputs: positional symbols
int MXTrSymbolCreate(const char* op_name, const char* name, void** inputs,
                     unsigned num_inputs, const char* attrs_json, void** out);
int MXTrSymbolFree(void* sym);

// -- executors --------------------------------------------------------------
// shapes_json: {"arg_name": [dims...], ...} for data/label inputs
int MXTrSimpleBind(void* sym, const char* shapes_json, void** out_exec);
int MXTrExecutorFree(void* exec);
// names are returned as a NUL-separated block (caller frees with MXTrBufFree)
int MXTrExecutorListArguments(void* exec, unsigned* num, char** names_blob);
int MXTrExecutorArgSize(void* exec, const char* name, unsigned* size);
int MXTrExecutorOutputSize(void* exec, unsigned index, unsigned* size);
int MXTrExecutorSetArg(void* exec, const char* name, const float* data,
                       unsigned size);
int MXTrExecutorGetArg(void* exec, const char* name, float* data,
                       unsigned size);
int MXTrExecutorGetGrad(void* exec, const char* name, float* data,
                        unsigned size);
int MXTrExecutorGetOutput(void* exec, unsigned index, float* data,
                          unsigned size);
int MXTrExecutorForward(void* exec, int is_train);
int MXTrExecutorBackward(void* exec);

// -- optimizers -------------------------------------------------------------
int MXTrOptimizerCreate(const char* type, const char* params_json, void** out);
int MXTrOptimizerFree(void* opt);
int MXTrOptimizerUpdate(void* opt, void* exec, const char* arg_name,
                        int index);

void MXTrBufFree(char* buf);

// finalize the interpreter this library started (no-op if the host started
// it). Call once before the process exits, after the last handle is freed
// (parity: MXNotifyShutdown at the end of the reference's C++ examples).
int MXTrShutdown();

#ifdef __cplusplus
}  // extern "C"
#endif

#endif  // MXTPU_C_TRAIN_API_H_
