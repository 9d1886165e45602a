/* Pure-PJRT native predictor — NO Python anywhere in the serving path.
 *
 * This is the final-deploy answer to the embedded-CPython predict shim
 * (predict.cc): it dlopens a PJRT plugin (libtpu.so on a TPU
 * machine), compiles the deploy artifact's StableHLO with
 * PJRT_Client_Compile, uploads the .pjrt_params.bin weights once, and
 * serves forwards straight through the PJRT C API.  N caller threads
 * never contend on any interpreter lock — there is none.  (Reference
 * role: c_predict_api.cc over the native engine +
 * cached_op_threadsafe.cc; VERDICT r3 Next #8, option A.)
 *
 * Artifact contract (written by deploy.export_model's PJRT sidecar):
 *   {prefix}.stablehlo.mlir    module text; main takes param leaves in
 *                              tree-flatten order, then user inputs
 *   {prefix}.pjrt.txt          argument/output manifest (line format)
 *   {prefix}.pjrt_params.bin   concatenated raw param bytes
 *   {prefix}.compile_options.pb serialized CompileOptionsProto
 *
 * Build: make -C src pjrt   (header-only dependency: pjrt_c_api.h)
 */
#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <mutex>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

thread_local std::string g_err;

void SetErr(std::string msg) { g_err = std::move(msg); }

int Fail(const PJRT_Api* api, PJRT_Error* err, const char* where) {
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = err;
  api->PJRT_Error_Message(&m);
  SetErr(std::string(where) + ": " + std::string(m.message, m.message_size));
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  api->PJRT_Error_Destroy(&d);
  return -1;
}

#define CHECK_PJRT(api, call, where)                  \
  do {                                                \
    PJRT_Error* _e = (call);                          \
    if (_e) return Fail((api), _e, (where));          \
  } while (0)

bool ReadFile(const std::string& path, std::string* out) {
  std::ifstream f(path, std::ios::binary);
  if (!f) return false;
  std::ostringstream ss;
  ss << f.rdbuf();
  *out = ss.str();
  return true;
}

struct ArgSpec {
  bool is_param = false;
  std::string dtype;
  int64_t offset = -1, nbytes = -1;
  std::vector<int64_t> dims;
};

struct OutSpec {
  std::string dtype;
  std::vector<int64_t> dims;
};

bool DtypeToPjrt(const std::string& d, PJRT_Buffer_Type* t, size_t* isz) {
  if (d == "float32") { *t = PJRT_Buffer_Type_F32; *isz = 4; return true; }
  if (d == "bfloat16") { *t = PJRT_Buffer_Type_BF16; *isz = 2; return true; }
  if (d == "float16") { *t = PJRT_Buffer_Type_F16; *isz = 2; return true; }
  if (d == "int32") { *t = PJRT_Buffer_Type_S32; *isz = 4; return true; }
  if (d == "int64") { *t = PJRT_Buffer_Type_S64; *isz = 8; return true; }
  if (d == "uint8") { *t = PJRT_Buffer_Type_U8; *isz = 1; return true; }
  if (d == "bool") { *t = PJRT_Buffer_Type_PRED; *isz = 1; return true; }
  return false;
}

struct Predictor {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  std::vector<ArgSpec> args;
  std::vector<OutSpec> outs;
  std::vector<PJRT_Buffer*> param_bufs;       // uploaded once
  std::vector<std::vector<char>> input_stage; // per input slot
  std::vector<bool> input_set;                // zero-size inputs are legal
  std::vector<size_t> input_slots;            // arg idx of each input
  std::vector<std::vector<char>> out_host;    // last forward's outputs
  bool have_output = false;
  std::mutex mu;                              // guards forward state
};

int AwaitEvent(const PJRT_Api* api, PJRT_Event* ev, const char* where) {
  PJRT_Event_Await_Args aw;
  std::memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&aw);
  PJRT_Event_Destroy_Args ed;
  std::memset(&ed, 0, sizeof(ed));
  ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  ed.event = ev;
  api->PJRT_Event_Destroy(&ed);
  if (e) return Fail(api, e, where);
  return 0;
}

int Upload(Predictor* p, const void* data, const ArgSpec& spec,
           PJRT_Buffer** out) {
  PJRT_Buffer_Type t;
  size_t isz;
  if (!DtypeToPjrt(spec.dtype, &t, &isz)) {
    SetErr("unsupported dtype " + spec.dtype);
    return -1;
  }
  PJRT_Client_BufferFromHostBuffer_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  a.client = p->client;
  a.data = data;
  a.type = t;
  a.dims = spec.dims.data();
  a.num_dims = spec.dims.size();
  a.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  a.device = p->device;
  CHECK_PJRT(p->api, p->api->PJRT_Client_BufferFromHostBuffer(&a),
             "BufferFromHostBuffer");
  if (a.done_with_host_buffer &&
      AwaitEvent(p->api, a.done_with_host_buffer, "host-buffer upload") != 0)
    return -1;
  *out = a.buffer;
  return 0;
}

void DestroyBuffer(Predictor* p, PJRT_Buffer* b) {
  if (!b) return;
  PJRT_Buffer_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  d.buffer = b;
  p->api->PJRT_Buffer_Destroy(&d);
}

}  // namespace

extern "C" {

int MXTPjrtPredFree(void* h);  // defined below; Create cleans up via it

const char* MXTPjrtLastError(void) { return g_err.c_str(); }

/* create_options: "k=v,k=v" — integer-looking values become kInt64,
 * everything else kString (PJRT plugins take their knobs this
 * way). */
int MXTPjrtPredCreate(const char* plugin_so, const char* create_options,
                      const char* prefix, void** out) {
  auto* p = new Predictor();
  p->dl = dlopen(plugin_so, RTLD_NOW | RTLD_LOCAL);
  if (!p->dl) {
    SetErr(std::string("dlopen ") + plugin_so + ": " + dlerror());
    delete p;
    return -1;
  }
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(p->dl, "GetPjrtApi"));
  if (!get_api) {
    SetErr(std::string(plugin_so) + " exports no GetPjrtApi");
    MXTPjrtPredFree(p);
    return -1;
  }
  p->api = get_api();

  // ---- parse options ----
  std::vector<std::string> keys, svals;
  std::vector<int64_t> ivals;
  std::vector<PJRT_NamedValue> options;
  if (create_options && *create_options) {
    std::stringstream ss(create_options);
    std::string kv;
    while (std::getline(ss, kv, ',')) {
      auto eq = kv.find('=');
      if (eq == std::string::npos) continue;
      keys.push_back(kv.substr(0, eq));
      svals.push_back(kv.substr(eq + 1));
    }
    ivals.resize(keys.size());
    for (size_t i = 0; i < keys.size(); ++i) {
      PJRT_NamedValue nv;
      std::memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = keys[i].c_str();
      nv.name_size = keys[i].size();
      char* end = nullptr;
      long long v = strtoll(svals[i].c_str(), &end, 10);
      if (end && *end == '\0' && !svals[i].empty()) {
        nv.type = PJRT_NamedValue_kInt64;
        ivals[i] = v;
        nv.int64_value = ivals[i];
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = svals[i].c_str();
        nv.value_size = svals[i].size();
      }
      options.push_back(nv);
    }
  }

  PJRT_Client_Create_Args c;
  std::memset(&c, 0, sizeof(c));
  c.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  c.create_options = options.data();
  c.num_options = options.size();
  {
    PJRT_Error* e = p->api->PJRT_Client_Create(&c);
    if (e) {
      int rc = Fail(p->api, e, "Client_Create");
      MXTPjrtPredFree(p);
      return rc;
    }
  }
  p->client = c.client;

  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof(ad));
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = p->client;
  {
    PJRT_Error* e = p->api->PJRT_Client_AddressableDevices(&ad);
    if (e) {
      int rc = Fail(p->api, e, "AddressableDevices");
      MXTPjrtPredFree(p);
      return rc;
    }
  }
  if (!ad.num_addressable_devices) {
    SetErr("no addressable devices");
    MXTPjrtPredFree(p);
    return -1;
  }
  p->device = ad.addressable_devices[0];

  // ---- manifest + program + options ----
  std::string pfx(prefix);
  std::string manifest, mlir, copts, params_bin;
  if (!ReadFile(pfx + ".pjrt.txt", &manifest) ||
      !ReadFile(pfx + ".stablehlo.mlir", &mlir) ||
      !ReadFile(pfx + ".pjrt_params.bin", &params_bin)) {
    SetErr("missing PJRT sidecar artifacts for " + pfx +
           " (re-export with a current deploy.export_model)");
    MXTPjrtPredFree(p);
    return -1;
  }
  if (!ReadFile(pfx + ".compile_options.pb", &copts)) {
    SetErr("missing " + pfx + ".compile_options.pb");
    MXTPjrtPredFree(p);
    return -1;
  }
  std::istringstream mf(manifest);
  std::string line;
  while (std::getline(mf, line)) {
    std::istringstream ls(line);
    std::string tag;
    ls >> tag;
    if (tag == "arg") {
      ArgSpec a;
      std::string kind;
      size_t nd;
      ls >> kind >> a.dtype >> a.offset >> a.nbytes >> nd;
      a.is_param = (kind == "param");
      a.dims.resize(nd);
      for (size_t i = 0; i < nd; ++i) ls >> a.dims[i];
      if (!a.is_param) p->input_slots.push_back(p->args.size());
      p->args.push_back(std::move(a));
    } else if (tag == "out") {
      OutSpec o;
      size_t nd;
      ls >> o.dtype >> nd;
      o.dims.resize(nd);
      for (size_t i = 0; i < nd; ++i) ls >> o.dims[i];
      p->outs.push_back(std::move(o));
    }
  }
  p->input_stage.resize(p->input_slots.size());
  p->input_set.assign(p->input_slots.size(), false);

  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = mlir.data();
  prog.code_size = mlir.size();
  prog.format = "mlir";
  prog.format_size = 4;

  PJRT_Client_Compile_Args cp;
  std::memset(&cp, 0, sizeof(cp));
  cp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  cp.client = p->client;
  cp.program = &prog;
  cp.compile_options = copts.data();
  cp.compile_options_size = copts.size();
  {
    PJRT_Error* e = p->api->PJRT_Client_Compile(&cp);
    if (e) {
      int rc = Fail(p->api, e, "Client_Compile");
      MXTPjrtPredFree(p);
      return rc;
    }
  }
  p->exec = cp.executable;

  // ---- upload params once ----
  for (auto& a : p->args) {
    if (!a.is_param) continue;
    if (a.offset < 0 ||
        size_t(a.offset + a.nbytes) > params_bin.size()) {
      SetErr("param manifest offsets out of range");
      MXTPjrtPredFree(p);
      return -1;
    }
    PJRT_Buffer* buf = nullptr;
    if (Upload(p, params_bin.data() + a.offset, a, &buf) != 0) {
      MXTPjrtPredFree(p);
      return -1;
    }
    p->param_bufs.push_back(buf);
  }
  *out = p;
  return 0;
}

int MXTPjrtPredSetInput(void* h, uint32_t index, const float* data,
                        uint64_t n_floats) {
  auto* p = static_cast<Predictor*>(h);
  if (index >= p->input_slots.size()) {
    SetErr("input index out of range");
    return -1;
  }
  const ArgSpec& spec = p->args[p->input_slots[index]];
  if (spec.dtype != "float32") {
    SetErr("C surface feeds float32 inputs; exported input is " +
           spec.dtype);
    return -1;
  }
  uint64_t want = 1;
  for (int64_t d : spec.dims) want *= (uint64_t)d;
  if (n_floats != want) {
    SetErr("input " + std::to_string(index) + " size mismatch: got " +
           std::to_string(n_floats) + " floats, exported shape needs " +
           std::to_string(want));
    return -1;
  }
  std::lock_guard<std::mutex> lk(p->mu);
  p->input_stage[index].assign(
      reinterpret_cast<const char*>(data),
      reinterpret_cast<const char*>(data) + n_floats * 4);
  p->input_set[index] = true;
  return 0;
}

int MXTPjrtPredForward(void* h) {
  auto* p = static_cast<Predictor*>(h);
  std::lock_guard<std::mutex> lk(p->mu);
  // assemble the argument list: params (persistent) + inputs (fresh)
  std::vector<PJRT_Buffer*> argv;
  std::vector<PJRT_Buffer*> fresh;
  size_t pi = 0, ii = 0;
  for (auto& a : p->args) {
    if (a.is_param) {
      argv.push_back(p->param_bufs[pi++]);
    } else {
      if (!p->input_set[ii]) {
        SetErr("input " + std::to_string(ii) + " not set");
        for (auto* b : fresh) DestroyBuffer(p, b);
        return -1;
      }
      PJRT_Buffer* buf = nullptr;
      if (Upload(p, p->input_stage[ii].data(), a, &buf) != 0) {
        for (auto* b : fresh) DestroyBuffer(p, b);
        return -1;
      }
      fresh.push_back(buf);
      argv.push_back(buf);
      ++ii;
    }
  }

  std::vector<PJRT_Buffer*> outv(p->outs.size(), nullptr);
  PJRT_Buffer* const* arg_list = argv.data();
  PJRT_Buffer** out_list = outv.data();
  PJRT_Event* done = nullptr;

  PJRT_ExecuteOptions eo;
  std::memset(&eo, 0, sizeof(eo));
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = p->exec;
  ex.options = &eo;
  ex.argument_lists = &arg_list;
  ex.num_devices = 1;
  ex.num_args = argv.size();
  ex.output_lists = &out_list;
  ex.device_complete_events = &done;
  {
    PJRT_Error* e = p->api->PJRT_LoadedExecutable_Execute(&ex);
    if (e) {
      for (auto* b : fresh) DestroyBuffer(p, b);
      return Fail(p->api, e, "Execute");
    }
  }
  int rc = done ? AwaitEvent(p->api, done, "execute completion") : 0;

  if (rc == 0) {
    p->have_output = false;
    p->out_host.assign(p->outs.size(), {});
    for (size_t i = 0; i < p->outs.size(); ++i) {
      PJRT_Buffer_ToHostBuffer_Args th;
      std::memset(&th, 0, sizeof(th));
      th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      th.src = outv[i];
      if (p->api->PJRT_Buffer_ToHostBuffer(&th)) {  // size query
        SetErr("ToHostBuffer size query failed");
        rc = -1;
        break;
      }
      p->out_host[i].resize(th.dst_size);
      th.dst = p->out_host[i].data();
      PJRT_Error* e = p->api->PJRT_Buffer_ToHostBuffer(&th);
      if (e) {
        rc = Fail(p->api, e, "ToHostBuffer");
        break;
      }
      if (th.event && AwaitEvent(p->api, th.event, "D2H copy") != 0) {
        rc = -1;
        break;
      }
    }
  }
  if (rc == 0) p->have_output = true;
  for (auto* b : fresh) DestroyBuffer(p, b);
  for (auto* b : outv) DestroyBuffer(p, b);
  return rc;
}

int MXTPjrtPredGetOutputSize(void* h, uint32_t index, uint64_t* size) {
  auto* p = static_cast<Predictor*>(h);
  if (index >= p->outs.size()) {
    SetErr("output index out of range");
    return -1;
  }
  uint64_t n = 1;
  for (int64_t d : p->outs[index].dims) n *= (uint64_t)d;
  *size = n;
  return 0;
}

int MXTPjrtPredGetOutput(void* h, uint32_t index, float* out,
                         uint64_t n_floats) {
  auto* p = static_cast<Predictor*>(h);
  std::lock_guard<std::mutex> lk(p->mu);
  if (!p->have_output || index >= p->out_host.size()) {
    SetErr("no output (call Forward first)");
    return -1;
  }
  const OutSpec& o = p->outs[index];
  const auto& raw = p->out_host[index];
  if (o.dtype == "float32") {
    if (raw.size() > n_floats * 4) {
      SetErr("output buffer too small");
      return -1;
    }
    std::memcpy(out, raw.data(), raw.size());
    return 0;
  }
  if (o.dtype == "bfloat16") {           // widen for the float C surface
    size_t n = raw.size() / 2;
    if (n > n_floats) {
      SetErr("output buffer too small");
      return -1;
    }
    const uint16_t* src = reinterpret_cast<const uint16_t*>(raw.data());
    for (size_t i = 0; i < n; ++i) {
      uint32_t bits = uint32_t(src[i]) << 16;
      std::memcpy(out + i, &bits, 4);
    }
    return 0;
  }
  SetErr("output dtype " + o.dtype + " not exposed via the float surface");
  return -1;
}

int MXTPjrtPredFree(void* h) {
  auto* p = static_cast<Predictor*>(h);
  if (!p) return 0;
  for (auto* b : p->param_bufs) DestroyBuffer(p, b);
  if (p->exec) {
    PJRT_LoadedExecutable_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    d.executable = p->exec;
    p->api->PJRT_LoadedExecutable_Destroy(&d);
  }
  if (p->client) {
    PJRT_Client_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    d.client = p->client;
    p->api->PJRT_Client_Destroy(&d);
  }
  if (p->dl) dlclose(p->dl);
  delete p;
  return 0;
}

}  // extern "C"
