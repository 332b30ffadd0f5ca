// Smoke CLI: run one inference on an exported .mxtpu artifact through a
// PJRT plugin, feeding deterministic ramp inputs, printing output shapes
// and leading values (reference parity: the amalgamation's
// mxnet_predict example / image-classification/predict-cpp).
//
//   mxtpu_predict <model.mxtpu> <pjrt_plugin.so> [--echo-input-check]
//       [--opt name=int:N | --opt name=str:S]...
//
// --echo-input-check asserts output 0 byte-equals input 0 (used by the
// mock-plugin test, whose Execute is an echo).
// --opt passes a NamedValue to PJRT_Client_Create — some plugins require
// plugin-specific create options.
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <stdexcept>
#include <string>
#include <vector>

#include "mxtpu/cli_opts.hpp"
#include "mxtpu/predictor.hpp"

int main(int argc, char** argv) {
  if (argc < 3) {
    std::fprintf(stderr,
                 "usage: %s <model.mxtpu> <pjrt_plugin.so> "
                 "[--echo-input-check] [--opt name=int:N|name=str:S]...\n",
                 argv[0]);
    return 2;
  }
  bool echo_check = false;
  std::vector<mxtpu::CreateOption> opts;
  try {
    for (int i = 3; i < argc; ++i) {
      if (std::strcmp(argv[i], "--echo-input-check") == 0) {
        echo_check = true;
      } else if (std::strcmp(argv[i], "--opt") == 0 && i + 1 < argc) {
        opts.push_back(mxtpu::ParseCliOpt(argv[++i]));
      } else {
        std::fprintf(stderr, "unknown argument: %s\n", argv[i]);
        return 2;
      }
    }
    mxtpu::Predictor pred(argv[1], argv[2], opts);
    std::printf("platform: %s\n", pred.platform().c_str());

    std::vector<mxtpu::Tensor> inputs;
    for (const mxtpu::Tensor& spec : pred.input_specs()) {
      mxtpu::Tensor t = spec;
      t.data.resize(t.byte_size());
      for (size_t i = 0; i < t.data.size(); ++i)
        t.data[i] = static_cast<uint8_t>(i % 251);
      inputs.push_back(std::move(t));
    }

    std::vector<mxtpu::Tensor> outs = pred.forward(inputs);
    for (size_t i = 0; i < outs.size(); ++i) {
      std::printf("output %zu: %s [", i, mxtpu::dtype_name(outs[i].dtype));
      for (size_t d = 0; d < outs[i].dims.size(); ++d)
        std::printf("%s%lld", d ? "," : "",
                    static_cast<long long>(outs[i].dims[d]));
      std::printf("] %zu bytes", outs[i].data.size());
      if (outs[i].dtype == mxtpu::DType::kF32 && !outs[i].data.empty()) {
        float v0;
        std::memcpy(&v0, outs[i].data.data(), sizeof(v0));
        std::printf(" first=%g", static_cast<double>(v0));
      }
      std::printf("\n");
    }
    if (echo_check) {
      if (outs.empty() || outs[0].data != inputs[0].data) {
        std::fprintf(stderr, "echo check FAILED\n");
        return 1;
      }
      std::printf("echo check OK\n");
    }
    return 0;
  } catch (const std::exception& e) {
    std::fprintf(stderr, "error: %s\n", e.what());
    return 1;
  }
}
