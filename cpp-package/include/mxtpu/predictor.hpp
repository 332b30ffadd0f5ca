// C++ inference API over exported .mxtpu artifacts.
//
// Parity: the reference's C++ prediction surface
// (cpp-package/include/mxnet-cpp/ + include/mxnet/c_predict_api.h:78-200 —
// MXPredCreate/SetInput/Forward/GetOutput). TPU-native redesign: instead of
// wrapping a framework C API, the predictor drives the PJRT C API directly —
// it dlopens any PJRT plugin (the TPU plugin, or any other conforming .so),
// compiles the artifact's StableHLO module bytecode, and executes it. No
// Python, no framework runtime, no protobuf/MLIR dependencies at build time.
//
// Artifact contract (written by mxnet_tpu/predict.py export_model):
// a STORE-only zip holding `model.mlir` (StableHLO bytecode) and
// `signature.txt` ("in|out <dtype> <d0>x<d1>..." per tensor).
#ifndef MXTPU_PREDICTOR_HPP_
#define MXTPU_PREDICTOR_HPP_

#include <cstdint>
#include <memory>
#include <string>
#include <vector>

namespace mxtpu {

enum class DType { kF32, kF16, kF64, kBF16, kS32, kS64, kS8, kU8, kPred };

size_t dtype_bytes(DType t);
const char* dtype_name(DType t);

struct Tensor {
  DType dtype = DType::kF32;
  std::vector<int64_t> dims;
  std::vector<uint8_t> data;  // dense, row-major (major-to-minor)

  int64_t num_elements() const;
  size_t byte_size() const { return num_elements() * dtype_bytes(dtype); }
};

// One PJRT_Client_Create NamedValue option. Some plugins refuse to create
// a client without plugin-specific options;
// the CLI exposes these as `--opt name=int:N` / `--opt name=str:S`.
struct CreateOption {
  std::string name;
  bool is_int = false;
  std::string str_value;
  int64_t int_value = 0;
};

class Predictor {
 public:
  // Loads `artifact_path` (.mxtpu zip), dlopens `plugin_so` (a PJRT
  // plugin), creates a client and compiles the module. Throws
  // std::runtime_error with the PJRT error message on failure.
  // `create_options` are passed to PJRT_Client_Create as NamedValues.
  Predictor(const std::string& artifact_path, const std::string& plugin_so,
            const std::vector<CreateOption>& create_options = {});
  ~Predictor();

  // Input/output specs from the artifact signature (data left empty).
  const std::vector<Tensor>& input_specs() const;
  const std::vector<Tensor>& output_specs() const;

  // PJRT platform name of the backing client, e.g. "tpu".
  const std::string& platform() const;

  // Runs one inference. `inputs` must match input_specs() in count, dtype,
  // dims, and byte size. Returns fully materialized host tensors.
  std::vector<Tensor> forward(const std::vector<Tensor>& inputs);

  // ---- training artifacts (export_train_step) -----------------------------
  // Input convention: [state_0..state_{K-1}, x, y, seed, lr, t];
  // outputs [loss, state'_0..state'_{K-1}]. State lives device-resident
  // across steps; only the per-step batch/scalars cross the host boundary.

  // True when the artifact carries `train.txt` (a training export).
  bool is_train() const;
  // Number of leading state inputs (0 for inference artifacts).
  size_t n_state() const;
  // The artifact's initial state values (`state/<i>.bin` blobs).
  std::vector<Tensor> initial_state() const;
  // Uploads `state` to the device as the resident training state.
  void load_state(const std::vector<Tensor>& state);
  // Runs one training step: `step_inputs` are the non-state inputs
  // (x, y, seed, lr, t). Returns the loss scalar; the new state replaces
  // the resident state on device. Requires load_state first.
  float train_step(const std::vector<Tensor>& step_inputs);
  // Downloads the resident state (for checkpointing).
  std::vector<Tensor> read_state();

  Predictor(const Predictor&) = delete;
  Predictor& operator=(const Predictor&) = delete;

 private:
  struct Impl;
  std::unique_ptr<Impl> impl_;
};

}  // namespace mxtpu

#endif  // MXTPU_PREDICTOR_HPP_
