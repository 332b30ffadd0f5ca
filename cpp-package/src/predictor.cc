// PJRT-driving implementation of mxtpu::Predictor. See predictor.hpp for
// the design rationale (reference parity: c_predict_api.cc, redesigned to
// speak the PJRT C API directly).
#include "mxtpu/predictor.hpp"

#include <dlfcn.h>

#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <stdexcept>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace mxtpu {

int64_t Tensor::num_elements() const {
  int64_t n = 1;
  for (int64_t d : dims) n *= d;
  return n;
}

size_t dtype_bytes(DType t) {
  switch (t) {
    case DType::kF64: case DType::kS64: return 8;
    case DType::kF32: case DType::kS32: return 4;
    case DType::kF16: case DType::kBF16: return 2;
    default: return 1;
  }
}

const char* dtype_name(DType t) {
  switch (t) {
    case DType::kF32: return "f32";
    case DType::kF16: return "f16";
    case DType::kF64: return "f64";
    case DType::kBF16: return "bf16";
    case DType::kS32: return "s32";
    case DType::kS64: return "s64";
    case DType::kS8: return "s8";
    case DType::kU8: return "u8";
    case DType::kPred: return "pred";
  }
  return "?";
}

namespace {

// ---------------------------------------------------------------------------
// minimal STORE-only zip reader (export_model writes with zipfile's default
// ZIP_STORED; compressed entries are rejected, not silently misread)
// ---------------------------------------------------------------------------

uint32_t rd32(const uint8_t* p) {
  return p[0] | (p[1] << 8) | (p[2] << 16) | (uint32_t(p[3]) << 24);
}
uint16_t rd16(const uint8_t* p) { return p[0] | (p[1] << 8); }

std::vector<uint8_t> read_file(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) throw std::runtime_error("cannot open artifact " + path);
  return std::vector<uint8_t>((std::istreambuf_iterator<char>(f)),
                              std::istreambuf_iterator<char>());
}

std::string read_zip_entry(const std::vector<uint8_t>& buf,
                           const std::string& name) {
  if (buf.size() < 22) throw std::runtime_error("artifact too small");
  // end-of-central-directory: scan back for PK\x05\x06
  size_t eocd = std::string::npos;
  for (size_t i = buf.size() - 22; i + 22 > 21; --i) {
    if (rd32(&buf[i]) == 0x06054b50) { eocd = i; break; }
    if (i == 0) break;
  }
  if (eocd == std::string::npos)
    throw std::runtime_error("not a zip artifact (no EOCD)");
  uint16_t n_entries = rd16(&buf[eocd + 10]);
  size_t off = rd32(&buf[eocd + 16]);
  for (uint16_t e = 0; e < n_entries; ++e) {
    if (off + 46 > buf.size() || rd32(&buf[off]) != 0x02014b50)
      throw std::runtime_error("corrupt zip central directory");
    uint16_t method = rd16(&buf[off + 10]);
    uint32_t csize = rd32(&buf[off + 20]);
    uint16_t name_len = rd16(&buf[off + 28]);
    uint16_t extra_len = rd16(&buf[off + 30]);
    uint16_t comment_len = rd16(&buf[off + 32]);
    uint32_t local_off = rd32(&buf[off + 42]);
    std::string entry(reinterpret_cast<const char*>(&buf[off + 46]), name_len);
    if (entry == name) {
      if (method != 0)
        throw std::runtime_error("zip entry " + name + " is compressed; "
                                 "artifacts must be STORE-only");
      // local header: skip its (possibly different) name/extra lengths
      if (local_off + 30 > buf.size() ||
          rd32(&buf[local_off]) != 0x04034b50)
        throw std::runtime_error("corrupt zip local header");
      uint16_t lname = rd16(&buf[local_off + 26]);
      uint16_t lextra = rd16(&buf[local_off + 28]);
      size_t data = local_off + 30 + lname + lextra;
      if (data + csize > buf.size())
        throw std::runtime_error("zip entry overruns file");
      return std::string(reinterpret_cast<const char*>(&buf[data]), csize);
    }
    off += 46 + name_len + extra_len + comment_len;
  }
  throw std::runtime_error("artifact has no entry " + name);
}

bool zip_has_entry(const std::vector<uint8_t>& buf,
                   const std::string& name) {
  try {
    read_zip_entry(buf, name);
    return true;
  } catch (const std::exception&) {
    return false;
  }
}

// ---------------------------------------------------------------------------
// signature.txt parsing
// ---------------------------------------------------------------------------

DType parse_dtype(const std::string& s) {
  if (s == "f32") return DType::kF32;
  if (s == "f16") return DType::kF16;
  if (s == "f64") return DType::kF64;
  if (s == "bf16") return DType::kBF16;
  if (s == "s32") return DType::kS32;
  if (s == "s64") return DType::kS64;
  if (s == "s8") return DType::kS8;
  if (s == "u8") return DType::kU8;
  if (s == "pred") return DType::kPred;
  throw std::runtime_error("signature has unknown dtype " + s);
}

PJRT_Buffer_Type pjrt_type(DType t) {
  switch (t) {
    case DType::kF32: return PJRT_Buffer_Type_F32;
    case DType::kF16: return PJRT_Buffer_Type_F16;
    case DType::kF64: return PJRT_Buffer_Type_F64;
    case DType::kBF16: return PJRT_Buffer_Type_BF16;
    case DType::kS32: return PJRT_Buffer_Type_S32;
    case DType::kS64: return PJRT_Buffer_Type_S64;
    case DType::kS8: return PJRT_Buffer_Type_S8;
    case DType::kU8: return PJRT_Buffer_Type_U8;
    case DType::kPred: return PJRT_Buffer_Type_PRED;
  }
  return PJRT_Buffer_Type_INVALID;
}

void parse_signature(const std::string& text, std::vector<Tensor>* ins,
                     std::vector<Tensor>* outs) {
  std::istringstream ss(text);
  std::string line;
  while (std::getline(ss, line)) {
    if (line.empty()) continue;
    std::istringstream ls(line);
    std::string role, dtype, dims;
    ls >> role >> dtype >> dims;
    Tensor t;
    t.dtype = parse_dtype(dtype);
    if (dims != "" && dims != "scalar") {
      std::istringstream ds(dims);
      std::string d;
      while (std::getline(ds, d, 'x')) t.dims.push_back(std::stoll(d));
    }
    if (role == "in") ins->push_back(std::move(t));
    else if (role == "out") outs->push_back(std::move(t));
    else throw std::runtime_error("signature has unknown role " + role);
  }
  if (outs->empty())
    throw std::runtime_error("signature declares no outputs");
}

// ---------------------------------------------------------------------------
// hand-rolled CompileOptionsProto (xla/pjrt/proto/compile_options.proto):
// executable_build_options{device_ordinal: -1, num_replicas: 1,
// num_partitions: 1} — the single-device default, no protobuf dependency
// ---------------------------------------------------------------------------

std::string compile_options_bytes() {
  std::string sub;
  sub += '\x08';                                   // field 1 varint
  for (int i = 0; i < 9; ++i) sub += '\xff';       // -1 as 64-bit varint
  sub += '\x01';
  sub += "\x20\x01";                               // field 4: num_replicas=1
  sub += "\x28\x01";                               // field 5: num_partitions=1
  std::string out;
  out += '\x1a';                                   // field 3 LEN
  out += static_cast<char>(sub.size());
  out += sub;
  return out;
}

}  // namespace

// ---------------------------------------------------------------------------
// Impl
// ---------------------------------------------------------------------------

struct Predictor::Impl {
  void* dso = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;
  std::string platform;
  std::vector<Tensor> input_specs;
  std::vector<Tensor> output_specs;
  // training artifacts: leading state inputs resident on device
  size_t n_state = 0;
  std::vector<Tensor> init_state;
  std::vector<PJRT_Buffer*> state_bufs;

  void destroy_buffer(PJRT_Buffer* b) {
    if (b == nullptr) return;
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = b;
    api->PJRT_Buffer_Destroy(&d);
  }

  PJRT_Buffer* upload(const Tensor& t, const Tensor& spec, size_t index) {
    if (t.dtype != spec.dtype || t.dims != spec.dims ||
        t.data.size() != spec.byte_size())
      throw std::runtime_error(
          "input " + std::to_string(index) + " does not match the artifact "
          "signature (want " + std::string(dtype_name(spec.dtype)) + ")");
    PJRT_Client_BufferFromHostBuffer_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = client;
    a.data = t.data.data();
    a.type = pjrt_type(t.dtype);
    a.dims = t.dims.data();
    a.num_dims = t.dims.size();
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    a.device = device;
    check(api->PJRT_Client_BufferFromHostBuffer(&a), "host->device");
    try {
      await(a.done_with_host_buffer, "host->device transfer");
    } catch (...) {
      destroy_buffer(a.buffer);  // not yet owned by any caller list
      throw;
    }
    return a.buffer;
  }

  // single-device execute over explicit buffer lists
  void execute(std::vector<PJRT_Buffer*>& in_bufs,
               std::vector<PJRT_Buffer*>& out_bufs) {
    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const* arg_list = in_bufs.data();
    PJRT_Buffer** out_list = out_bufs.data();
    PJRT_LoadedExecutable_Execute_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    a.executable = exec;
    a.options = &opts;
    a.argument_lists = &arg_list;
    a.num_devices = 1;
    a.num_args = in_bufs.size();
    a.output_lists = &out_list;
    check(api->PJRT_LoadedExecutable_Execute(&a), "execute");
  }

  Tensor download(PJRT_Buffer* buf, const Tensor& spec) {
    Tensor t = spec;  // dtype + dims from the signature
    PJRT_Buffer_ToHostBuffer_Args h;
    std::memset(&h, 0, sizeof(h));
    h.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    h.src = buf;
    check(api->PJRT_Buffer_ToHostBuffer(&h), "output size query");
    await(h.event, "output size query");  // null for size-only queries
    t.data.resize(h.dst_size);
    std::memset(&h, 0, sizeof(h));
    h.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    h.src = buf;
    h.dst = t.data.data();
    h.dst_size = t.data.size();
    check(api->PJRT_Buffer_ToHostBuffer(&h), "device->host");
    await(h.event, "device->host transfer");
    return t;
  }

  void check(PJRT_Error* err, const char* what) {
    if (err == nullptr) return;
    PJRT_Error_Message_Args m;
    std::memset(&m, 0, sizeof(m));
    m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    m.error = err;
    api->PJRT_Error_Message(&m);
    std::string msg(m.message, m.message_size);
    PJRT_Error_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    d.error = err;
    api->PJRT_Error_Destroy(&d);
    throw std::runtime_error(std::string(what) + ": " + msg);
  }

  void await(PJRT_Event* ev, const char* what) {
    if (ev == nullptr) return;
    PJRT_Event_Await_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    a.event = ev;
    PJRT_Error* err = api->PJRT_Event_Await(&a);
    PJRT_Event_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    d.event = ev;
    api->PJRT_Event_Destroy(&d);
    check(err, what);
  }

  ~Impl() {
    if (api != nullptr) {
      for (PJRT_Buffer* b : state_bufs) destroy_buffer(b);
      if (exec != nullptr) {
        PJRT_LoadedExecutable_Destroy_Args a;
        std::memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
        a.executable = exec;
        api->PJRT_LoadedExecutable_Destroy(&a);
      }
      if (client != nullptr) {
        PJRT_Client_Destroy_Args a;
        std::memset(&a, 0, sizeof(a));
        a.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
        a.client = client;
        api->PJRT_Client_Destroy(&a);
      }
    }
    if (dso != nullptr) dlclose(dso);
  }
};

Predictor::Predictor(const std::string& artifact_path,
                     const std::string& plugin_so,
                     const std::vector<CreateOption>& create_options)
    : impl_(new Impl()) {
  Impl& im = *impl_;
  std::vector<uint8_t> zip = read_file(artifact_path);
  std::string mlir = read_zip_entry(zip, "model.mlir");
  parse_signature(read_zip_entry(zip, "signature.txt"),
                  &im.input_specs, &im.output_specs);
  if (zip_has_entry(zip, "train.txt")) {
    std::istringstream ts(read_zip_entry(zip, "train.txt"));
    std::string word;
    ts >> word >> im.n_state;
    if (word != "n_state" || im.n_state == 0 ||
        im.n_state + 5 != im.input_specs.size() ||
        im.n_state + 1 != im.output_specs.size())
      throw std::runtime_error(
          "train.txt n_state inconsistent with the signature");
    for (size_t i = 0; i < im.n_state; ++i) {
      // output 1+i chains into input i next step: specs must agree, or
      // step 2 would feed wrong-shaped buffers into the executable
      if (im.output_specs[1 + i].dtype != im.input_specs[i].dtype ||
          im.output_specs[1 + i].dims != im.input_specs[i].dims)
        throw std::runtime_error(
            "state " + std::to_string(i) + ": output spec does not match "
            "input spec (broken chain in the artifact signature)");
      Tensor t = im.input_specs[i];
      std::string blob =
          read_zip_entry(zip, "state/" + std::to_string(i) + ".bin");
      if (blob.size() != t.byte_size())
        throw std::runtime_error("state blob " + std::to_string(i) +
                                 " size mismatch with signature");
      t.data.assign(blob.begin(), blob.end());
      im.init_state.push_back(std::move(t));
    }
  }
  zip.clear();
  zip.shrink_to_fit();

  im.dso = dlopen(plugin_so.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (im.dso == nullptr)
    throw std::runtime_error(std::string("dlopen failed: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(im.dso, "GetPjrtApi"));
  if (get_api == nullptr)
    throw std::runtime_error(plugin_so + " exports no GetPjrtApi");
  im.api = get_api();
  if (im.api == nullptr)
    throw std::runtime_error("GetPjrtApi returned null");

  // MXTPU_VERBOSE=1: stage markers on stderr, so a hang inside a plugin
  // (client create, compile) is localizable from logs
  const bool verbose = [] {
    const char* v = std::getenv("MXTPU_VERBOSE");
    return v != nullptr && v[0] == '1';
  }();
  auto stage = [&](const char* what) {
    if (verbose) {
      std::fprintf(stderr, "[mxtpu] %s...\n", what);
      std::fflush(stderr);
    }
  };

  stage("plugin init");
  {
    PJRT_Plugin_Initialize_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    im.check(im.api->PJRT_Plugin_Initialize(&a), "plugin init");
  }
  stage("client create");
  {
    std::vector<PJRT_NamedValue> nvs(create_options.size());
    for (size_t i = 0; i < create_options.size(); ++i) {
      const CreateOption& o = create_options[i];
      PJRT_NamedValue& nv = nvs[i];
      std::memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = o.name.c_str();
      nv.name_size = o.name.size();
      if (o.is_int) {
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = o.int_value;
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = o.str_value.c_str();
        nv.value_size = o.str_value.size();
      }
    }
    PJRT_Client_Create_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    a.create_options = nvs.empty() ? nullptr : nvs.data();
    a.num_options = nvs.size();
    im.check(im.api->PJRT_Client_Create(&a), "client create");
    im.client = a.client;
  }
  {
    PJRT_Client_PlatformName_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
    a.client = im.client;
    im.check(im.api->PJRT_Client_PlatformName(&a), "platform name");
    im.platform.assign(a.platform_name, a.platform_name_size);
  }
  {
    PJRT_Client_AddressableDevices_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    a.client = im.client;
    im.check(im.api->PJRT_Client_AddressableDevices(&a), "devices");
    if (a.num_addressable_devices == 0)
      throw std::runtime_error("client has no addressable devices");
    im.device = a.addressable_devices[0];
  }
  stage("compile");
  {
    std::string opts = compile_options_bytes();
    PJRT_Program program;
    std::memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = mlir.data();
    program.code_size = mlir.size();
    static const char kFormat[] = "mlir";
    program.format = kFormat;
    program.format_size = sizeof(kFormat) - 1;
    PJRT_Client_Compile_Args a;
    std::memset(&a, 0, sizeof(a));
    a.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    a.client = im.client;
    a.program = &program;
    a.compile_options = opts.data();
    a.compile_options_size = opts.size();
    im.check(im.api->PJRT_Client_Compile(&a), "compile");
    im.exec = a.executable;
  }
  // the signature drives output buffer allocation; a mismatch with the
  // compiled module would corrupt the output_lists array, so verify it
  // (skipped only when the plugin doesn't serve the introspection calls)
  if (im.api->PJRT_LoadedExecutable_GetExecutable != nullptr &&
      im.api->PJRT_Executable_NumOutputs != nullptr) {
    PJRT_LoadedExecutable_GetExecutable_Args g;
    std::memset(&g, 0, sizeof(g));
    g.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    g.loaded_executable = im.exec;
    im.check(im.api->PJRT_LoadedExecutable_GetExecutable(&g),
             "get executable");
    PJRT_Executable_NumOutputs_Args n;
    std::memset(&n, 0, sizeof(n));
    n.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    n.executable = g.executable;
    PJRT_Error* nerr = im.api->PJRT_Executable_NumOutputs(&n);
    if (im.api->PJRT_Executable_Destroy != nullptr) {
      PJRT_Executable_Destroy_Args d;
      std::memset(&d, 0, sizeof(d));
      d.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
      d.executable = g.executable;
      im.api->PJRT_Executable_Destroy(&d);
    }
    im.check(nerr, "num outputs");
    if (n.num_outputs != im.output_specs.size())
      throw std::runtime_error(
          "artifact signature declares " +
          std::to_string(im.output_specs.size()) + " outputs but the "
          "compiled module produces " + std::to_string(n.num_outputs));
  }
}

Predictor::~Predictor() = default;

const std::vector<Tensor>& Predictor::input_specs() const {
  return impl_->input_specs;
}
const std::vector<Tensor>& Predictor::output_specs() const {
  return impl_->output_specs;
}
const std::string& Predictor::platform() const { return impl_->platform; }

std::vector<Tensor> Predictor::forward(const std::vector<Tensor>& inputs) {
  Impl& im = *impl_;
  if (inputs.size() != im.input_specs.size())
    throw std::runtime_error("expected " +
                             std::to_string(im.input_specs.size()) +
                             " inputs, got " + std::to_string(inputs.size()));
  std::vector<PJRT_Buffer*> in_bufs;
  std::vector<PJRT_Buffer*> out_bufs(im.output_specs.size(), nullptr);
  auto destroy_bufs = [&](std::vector<PJRT_Buffer*>& bufs) {
    for (PJRT_Buffer* b : bufs) im.destroy_buffer(b);
    bufs.clear();
  };
  try {
    for (size_t i = 0; i < inputs.size(); ++i)
      in_bufs.push_back(im.upload(inputs[i], im.input_specs[i], i));
    im.execute(in_bufs, out_bufs);
    std::vector<Tensor> outs;
    for (size_t i = 0; i < out_bufs.size(); ++i)
      outs.push_back(im.download(out_bufs[i], im.output_specs[i]));
    destroy_bufs(in_bufs);
    destroy_bufs(out_bufs);
    return outs;
  } catch (...) {
    destroy_bufs(in_bufs);
    destroy_bufs(out_bufs);
    throw;
  }
}

// ---------------------------------------------------------------------------
// training-artifact API (export_train_step convention)
// ---------------------------------------------------------------------------

bool Predictor::is_train() const { return impl_->n_state > 0; }
size_t Predictor::n_state() const { return impl_->n_state; }

std::vector<Tensor> Predictor::initial_state() const {
  return impl_->init_state;
}

void Predictor::load_state(const std::vector<Tensor>& state) {
  Impl& im = *impl_;
  if (!is_train())
    throw std::runtime_error("load_state: not a training artifact");
  if (state.size() != im.n_state)
    throw std::runtime_error("load_state: expected " +
                             std::to_string(im.n_state) + " tensors, got " +
                             std::to_string(state.size()));
  std::vector<PJRT_Buffer*> bufs;
  try {
    for (size_t i = 0; i < state.size(); ++i)
      bufs.push_back(im.upload(state[i], im.input_specs[i], i));
  } catch (...) {
    for (PJRT_Buffer* b : bufs) im.destroy_buffer(b);
    throw;
  }
  for (PJRT_Buffer* b : im.state_bufs) im.destroy_buffer(b);
  im.state_bufs = std::move(bufs);
}

float Predictor::train_step(const std::vector<Tensor>& step_inputs) {
  Impl& im = *impl_;
  if (im.state_bufs.size() != im.n_state || im.n_state == 0)
    throw std::runtime_error("train_step: call load_state first");
  size_t n_step = im.input_specs.size() - im.n_state;  // x, y, seed, lr, t
  if (step_inputs.size() != n_step)
    throw std::runtime_error("train_step: expected " +
                             std::to_string(n_step) + " step inputs, got " +
                             std::to_string(step_inputs.size()));
  std::vector<PJRT_Buffer*> fed;     // uploaded batch/scalars (freed here)
  std::vector<PJRT_Buffer*> out_bufs(im.output_specs.size(), nullptr);
  try {
    std::vector<PJRT_Buffer*> args(im.state_bufs);
    for (size_t i = 0; i < step_inputs.size(); ++i) {
      fed.push_back(im.upload(step_inputs[i],
                              im.input_specs[im.n_state + i],
                              im.n_state + i));
      args.push_back(fed.back());
    }
    im.execute(args, out_bufs);
    Tensor loss_t = im.download(out_bufs[0], im.output_specs[0]);
    if (loss_t.dtype != DType::kF32 || loss_t.data.size() != 4)
      throw std::runtime_error("train artifact loss is not a f32 scalar");
    float loss;
    std::memcpy(&loss, loss_t.data.data(), 4);
    // chain: new state replaces the resident buffers; old state + fed
    // inputs + the loss buffer are done
    for (PJRT_Buffer* b : im.state_bufs) im.destroy_buffer(b);
    im.state_bufs.assign(out_bufs.begin() + 1, out_bufs.end());
    im.destroy_buffer(out_bufs[0]);
    for (PJRT_Buffer* b : fed) im.destroy_buffer(b);
    return loss;
  } catch (...) {
    for (PJRT_Buffer* b : fed) im.destroy_buffer(b);
    for (PJRT_Buffer* b : out_bufs) im.destroy_buffer(b);
    throw;
  }
}

std::vector<Tensor> Predictor::read_state() {
  Impl& im = *impl_;
  if (im.state_bufs.size() != im.n_state || im.n_state == 0)
    throw std::runtime_error("read_state: call load_state first");
  std::vector<Tensor> out;
  for (size_t i = 0; i < im.state_bufs.size(); ++i)
    out.push_back(im.download(im.state_bufs[i], im.input_specs[i]));
  return out;
}

}  // namespace mxtpu
