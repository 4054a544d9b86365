// Minimal C++ PJRT client — the native tensor-runtime boundary.
//
// The reference delegates all native math to the external ND4J backends
// (nd4j-x86 BLAS / nd4j-jcublas CUDA, SURVEY.md §2.9); our equivalent
// native layer speaks PJRT, the C ABI every XLA backend (TPU, CPU, GPU)
// plugs into. This client does the §7-stage-1 minimum: dlopen a PJRT
// plugin (e.g. the TPU plugin), create a client, enumerate devices,
// compile a StableHLO module, and execute it on device buffers — proving
// the non-Python path to the same accelerator JAX drives.
//
// C ABI (ctypes-friendly, mirrors dl4j_native.cpp conventions): all
// functions return 0/handle on success; error text is copied into the
// caller's buffer. Thread-safety: a handle must not be shared across
// threads without external locking.
//
// Build: make pjrt PJRT_INCLUDE=<dir containing tensorflow/compiler/...>
// (header-only dependency; the plugin .so is loaded at runtime).

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

struct Handle {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
};

void set_err(char* err, int errn, const std::string& msg) {
  if (err && errn > 0) {
    std::snprintf(err, size_t(errn), "%s", msg.c_str());
  }
}

// Returns true (and fills err) when `e` is an error; destroys it.
bool take_error(const PJRT_Api* api, PJRT_Error* e, char* err, int errn) {
  if (e == nullptr) return false;
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = e;
  api->PJRT_Error_Message(&m);
  set_err(err, errn, std::string(m.message, m.message_size));
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = e;
  api->PJRT_Error_Destroy(&d);
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, char* err, int errn) {
  if (ev == nullptr) return true;
  PJRT_Event_Await_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&a);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  api->PJRT_Event_Destroy(&d);
  return !take_error(api, e, err, errn);
}

}  // namespace

// Parse "i:name=123;s:name=text;..." into NamedValues. Strings backing
// the values live in `names`/`strs` (caller keeps them alive through
// Client_Create).
static void parse_options(const char* spec, std::vector<std::string>* names,
                          std::vector<std::string>* strs,
                          std::vector<int64_t>* ints,
                          std::vector<PJRT_NamedValue>* out) {
  if (!spec) return;
  std::string s(spec);
  // Two passes: materialize owned strings/ints first so pointers into
  // the vectors stay stable when building the NamedValues.
  struct Entry { char kind; std::string name; std::string val; };
  std::vector<Entry> entries;
  size_t pos = 0;
  while (pos < s.size()) {
    size_t end = s.find(';', pos);
    if (end == std::string::npos) end = s.size();
    std::string item = s.substr(pos, end - pos);
    pos = end + 1;
    if (item.size() < 4 || item[1] != ':') continue;
    size_t eq = item.find('=', 2);
    if (eq == std::string::npos) continue;
    entries.push_back({item[0], item.substr(2, eq - 2),
                       item.substr(eq + 1)});
  }
  names->reserve(entries.size());
  strs->reserve(entries.size());
  ints->reserve(entries.size());
  for (const auto& e : entries) {
    names->push_back(e.name);
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = names->back().c_str();
    nv.name_size = names->back().size();
    if (e.kind == 'i') {
      ints->push_back(std::strtoll(e.val.c_str(), nullptr, 10));
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = ints->back();
      nv.value_size = 1;
    } else {
      strs->push_back(e.val);
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = strs->back().c_str();
      nv.value_size = strs->back().size();
    }
    out->push_back(nv);
  }
}

extern "C" {

// Load `plugin_path`, initialize it, create a client. `options` is an
// optional plugin-option spec "i:key=123;s:key=text;..." (NamedValues;
// libtpu needs none).
// NULL on failure.
void* dl4j_pjrt_open(const char* plugin_path, const char* options,
                     char* err, int errn) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) {
    set_err(err, errn, std::string("dlopen: ") + dlerror());
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errn, "GetPjrtApi symbol not found");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();

  PJRT_Plugin_Initialize_Args init;
  std::memset(&init, 0, sizeof(init));
  init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (take_error(api, api->PJRT_Plugin_Initialize(&init), err, errn)) {
    dlclose(dl);
    return nullptr;
  }

  std::vector<std::string> names, strs;
  std::vector<int64_t> ints;
  std::vector<PJRT_NamedValue> nvs;
  parse_options(options, &names, &strs, &ints, &nvs);

  PJRT_Client_Create_Args cc;
  std::memset(&cc, 0, sizeof(cc));
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.create_options = nvs.empty() ? nullptr : nvs.data();
  cc.num_options = nvs.size();
  if (take_error(api, api->PJRT_Client_Create(&cc), err, errn)) {
    dlclose(dl);
    return nullptr;
  }
  auto* h = new Handle{dl, api, cc.client};
  return h;
}

void dl4j_pjrt_close(void* handle) {
  auto* h = static_cast<Handle*>(handle);
  if (!h) return;
  if (h->client) {
    PJRT_Client_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    d.client = h->client;
    h->api->PJRT_Client_Destroy(&d);
  }
  if (h->dl) dlclose(h->dl);
  delete h;
}

int dl4j_pjrt_device_count(void* handle) {
  auto* h = static_cast<Handle*>(handle);
  PJRT_Client_AddressableDevices_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  a.client = h->client;
  if (take_error(h->api, h->api->PJRT_Client_AddressableDevices(&a),
                 nullptr, 0)) {
    return -1;
  }
  return int(a.num_addressable_devices);
}

int dl4j_pjrt_platform(void* handle, char* out, int n) {
  auto* h = static_cast<Handle*>(handle);
  PJRT_Client_PlatformName_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  a.client = h->client;
  if (take_error(h->api, h->api->PJRT_Client_PlatformName(&a), nullptr, 0)) {
    return -1;
  }
  int len = int(a.platform_name_size) < n - 1 ? int(a.platform_name_size)
                                              : n - 1;
  std::memcpy(out, a.platform_name, size_t(len));
  out[len] = 0;
  return len;
}

// Compile `code` (StableHLO text or VHLO/MLIR bytecode, `code_size`
// bytes) with the serialized CompileOptionsProto in `copts` (may be
// empty), then run with one f32 input of shape in_dims[0..in_nd); the
// executable's single f32 output is copied into `out` (capacity
// `out_capacity` floats). Returns the number of output floats, or -1
// (error text in `err`).
int64_t dl4j_pjrt_run_f32(void* handle, const char* code,
                          int64_t code_size, const char* copts,
                          int64_t copts_size,
                          const float* in, const int64_t* in_dims,
                          int32_t in_nd, float* out, int64_t out_capacity,
                          char* err, int errn) {
  auto* h = static_cast<Handle*>(handle);
  const PJRT_Api* api = h->api;

  // -- compile -------------------------------------------------------
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(code);
  prog.code_size = size_t(code_size);
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args comp;
  std::memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = h->client;
  comp.program = &prog;
  comp.compile_options = copts ? copts : "";
  comp.compile_options_size = size_t(copts_size);
  if (take_error(api, api->PJRT_Client_Compile(&comp), err, errn)) return -1;
  PJRT_LoadedExecutable* exe = comp.executable;

  auto destroy_exe = [&]() {
    PJRT_LoadedExecutable_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    d.executable = exe;
    api->PJRT_LoadedExecutable_Destroy(&d);
  };

  // -- host -> device ------------------------------------------------
  PJRT_Client_AddressableDevices_Args devs;
  std::memset(&devs, 0, sizeof(devs));
  devs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  devs.client = h->client;
  if (take_error(api, api->PJRT_Client_AddressableDevices(&devs), err,
                 errn)) {
    destroy_exe();
    return -1;
  }
  if (devs.num_addressable_devices == 0) {
    set_err(err, errn, "no addressable devices");
    destroy_exe();
    return -1;
  }

  PJRT_Client_BufferFromHostBuffer_Args hb;
  std::memset(&hb, 0, sizeof(hb));
  hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  hb.client = h->client;
  hb.data = in;
  hb.type = PJRT_Buffer_Type_F32;
  hb.dims = in_dims;
  hb.num_dims = size_t(in_nd);
  hb.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  hb.device = devs.addressable_devices[0];
  if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&hb), err,
                 errn)) {
    destroy_exe();
    return -1;
  }
  PJRT_Buffer* in_buf = hb.buffer;
  auto destroy_buf = [&](PJRT_Buffer* b) {
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = b;
    api->PJRT_Buffer_Destroy(&d);
  };
  if (!await_event(api, hb.done_with_host_buffer, err, errn)) {
    destroy_buf(in_buf);
    destroy_exe();
    return -1;
  }

  // -- execute (1 device, 1 arg, 1 output) ---------------------------
  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer* args_dev0[1] = {in_buf};
  PJRT_Buffer* const* arg_lists[1] = {args_dev0};
  PJRT_Buffer* out_dev0[1] = {nullptr};
  PJRT_Buffer** out_lists[1] = {out_dev0};
  PJRT_Event* done[1] = {nullptr};

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = exe;
  ex.options = &opts;
  ex.argument_lists = arg_lists;
  ex.num_devices = 1;
  ex.num_args = 1;
  ex.output_lists = out_lists;
  ex.device_complete_events = done;
  bool exec_failed =
      take_error(api, api->PJRT_LoadedExecutable_Execute(&ex), err, errn);
  destroy_buf(in_buf);
  if (exec_failed) {
    destroy_exe();
    return -1;
  }
  if (!await_event(api, done[0], err, errn)) {
    if (out_dev0[0]) destroy_buf(out_dev0[0]);
    destroy_exe();
    return -1;
  }
  PJRT_Buffer* out_buf = out_dev0[0];

  // -- device -> host ------------------------------------------------
  // Request a dense ROW-MAJOR host layout explicitly: with
  // host_layout=nullptr the copy uses the device buffer's layout, and
  // TPU buffers are frequently column-major/tiled — the bytes would
  // arrive permuted.
  PJRT_Buffer_Dimensions_Args bd;
  std::memset(&bd, 0, sizeof(bd));
  bd.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  bd.buffer = out_buf;
  if (take_error(api, api->PJRT_Buffer_Dimensions(&bd), err, errn)) {
    destroy_buf(out_buf);
    destroy_exe();
    return -1;
  }
  std::vector<int64_t> minor_to_major(bd.num_dims);
  for (size_t i = 0; i < bd.num_dims; ++i) {
    minor_to_major[i] = int64_t(bd.num_dims - 1 - i);
  }
  PJRT_Buffer_MemoryLayout row_major;
  std::memset(&row_major, 0, sizeof(row_major));
  row_major.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  row_major.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  row_major.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  row_major.tiled.minor_to_major = minor_to_major.data();
  row_major.tiled.minor_to_major_size = minor_to_major.size();

  PJRT_Buffer_ToHostBuffer_Args th;
  std::memset(&th, 0, sizeof(th));
  th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  th.src = out_buf;
  th.host_layout = &row_major;
  th.dst = nullptr;  // query size
  if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&th), err, errn)) {
    destroy_buf(out_buf);
    destroy_exe();
    return -1;
  }
  int64_t n_floats = int64_t(th.dst_size / sizeof(float));
  if (n_floats > out_capacity) {
    set_err(err, errn, "output larger than caller capacity");
    destroy_buf(out_buf);
    destroy_exe();
    return -1;
  }
  th.dst = out;
  bool copy_failed =
      take_error(api, api->PJRT_Buffer_ToHostBuffer(&th), err, errn);
  if (!copy_failed) copy_failed = !await_event(api, th.event, err, errn);
  destroy_buf(out_buf);
  destroy_exe();
  return copy_failed ? -1 : n_floats;
}

// ---------------------------------------------------------------------
// Serving API (round 4): compile ONCE, execute repeatedly with N args
// and M outputs, buffers staying device-resident between steps — the
// shape a KV-cache decode loop needs (per-step recompile or per-step
// host round-trips of the cache would dominate decode latency).
// ---------------------------------------------------------------------

void* dl4j_pjrt_compile(void* handle, const char* code, int64_t code_size,
                        const char* copts, int64_t copts_size, char* err,
                        int errn) {
  auto* h = static_cast<Handle*>(handle);
  const PJRT_Api* api = h->api;
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(code);
  prog.code_size = size_t(code_size);
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;
  PJRT_Client_Compile_Args comp;
  std::memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = h->client;
  comp.program = &prog;
  comp.compile_options = copts ? copts : "";
  comp.compile_options_size = size_t(copts_size);
  if (take_error(api, api->PJRT_Client_Compile(&comp), err, errn)) {
    return nullptr;
  }
  return comp.executable;
}

void dl4j_pjrt_exe_destroy(void* handle, void* exe) {
  auto* h = static_cast<Handle*>(handle);
  PJRT_LoadedExecutable_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  d.executable = static_cast<PJRT_LoadedExecutable*>(exe);
  h->api->PJRT_LoadedExecutable_Destroy(&d);
}

void* dl4j_pjrt_buffer_from_host_f32(void* handle, const float* in,
                                     const int64_t* dims, int32_t nd,
                                     char* err, int errn) {
  auto* h = static_cast<Handle*>(handle);
  const PJRT_Api* api = h->api;
  PJRT_Client_AddressableDevices_Args devs;
  std::memset(&devs, 0, sizeof(devs));
  devs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  devs.client = h->client;
  if (take_error(api, api->PJRT_Client_AddressableDevices(&devs), err,
                 errn)) {
    return nullptr;
  }
  if (devs.num_addressable_devices == 0) {
    set_err(err, errn, "no addressable devices");
    return nullptr;
  }
  PJRT_Client_BufferFromHostBuffer_Args hb;
  std::memset(&hb, 0, sizeof(hb));
  hb.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  hb.client = h->client;
  hb.data = in;
  hb.type = PJRT_Buffer_Type_F32;
  hb.dims = dims;
  hb.num_dims = size_t(nd);
  hb.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  hb.device = devs.addressable_devices[0];
  if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&hb), err,
                 errn)) {
    return nullptr;
  }
  if (!await_event(api, hb.done_with_host_buffer, err, errn)) {
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = hb.buffer;
    api->PJRT_Buffer_Destroy(&d);
    return nullptr;
  }
  return hb.buffer;
}

void dl4j_pjrt_buffer_destroy(void* handle, void* buf) {
  auto* h = static_cast<Handle*>(handle);
  PJRT_Buffer_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  d.buffer = static_cast<PJRT_Buffer*>(buf);
  h->api->PJRT_Buffer_Destroy(&d);
}

int64_t dl4j_pjrt_buffer_to_host_f32(void* handle, void* buf, float* out,
                                     int64_t out_capacity, char* err,
                                     int errn) {
  auto* h = static_cast<Handle*>(handle);
  const PJRT_Api* api = h->api;
  auto* b = static_cast<PJRT_Buffer*>(buf);
  PJRT_Buffer_Dimensions_Args bd;
  std::memset(&bd, 0, sizeof(bd));
  bd.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  bd.buffer = b;
  if (take_error(api, api->PJRT_Buffer_Dimensions(&bd), err, errn)) {
    return -1;
  }
  std::vector<int64_t> minor_to_major(bd.num_dims);
  for (size_t i = 0; i < bd.num_dims; ++i) {
    minor_to_major[i] = int64_t(bd.num_dims - 1 - i);
  }
  PJRT_Buffer_MemoryLayout row_major;
  std::memset(&row_major, 0, sizeof(row_major));
  row_major.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  row_major.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  row_major.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  row_major.tiled.minor_to_major = minor_to_major.data();
  row_major.tiled.minor_to_major_size = minor_to_major.size();
  PJRT_Buffer_ToHostBuffer_Args th;
  std::memset(&th, 0, sizeof(th));
  th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  th.src = b;
  th.host_layout = &row_major;
  th.dst = nullptr;  // query size
  if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&th), err, errn)) {
    return -1;
  }
  int64_t n_floats = int64_t(th.dst_size / sizeof(float));
  if (n_floats > out_capacity) {
    set_err(err, errn, "output larger than caller capacity");
    return -1;
  }
  th.dst = out;
  if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&th), err, errn)) {
    return -1;
  }
  if (!await_event(api, th.event, err, errn)) return -1;
  return n_floats;
}

int64_t dl4j_pjrt_execute(void* handle, void* exe, void** in_bufs,
                          int32_t n_in, void** out_bufs,
                          int32_t out_capacity, char* err, int errn) {
  auto* h = static_cast<Handle*>(handle);
  const PJRT_Api* api = h->api;
  auto* e = static_cast<PJRT_LoadedExecutable*>(exe);

  // number of outputs from the wrapped executable
  PJRT_LoadedExecutable_GetExecutable_Args ge;
  std::memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = e;
  if (take_error(api, api->PJRT_LoadedExecutable_GetExecutable(&ge), err,
                 errn)) {
    return -1;
  }
  PJRT_Executable_NumOutputs_Args no;
  std::memset(&no, 0, sizeof(no));
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  if (take_error(api, api->PJRT_Executable_NumOutputs(&no), err, errn)) {
    return -1;
  }
  int64_t n_out = int64_t(no.num_outputs);
  if (n_out > out_capacity) {
    set_err(err, errn, "more outputs than caller capacity");
    return -1;
  }

  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> args(static_cast<size_t>(n_in));
  for (int32_t i = 0; i < n_in; ++i) {
    args[size_t(i)] = static_cast<PJRT_Buffer*>(in_bufs[i]);
  }
  PJRT_Buffer* const* arg_lists[1] = {args.data()};
  std::vector<PJRT_Buffer*> outs(size_t(n_out), nullptr);
  PJRT_Buffer** out_lists[1] = {outs.data()};
  PJRT_Event* done[1] = {nullptr};

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = e;
  ex.options = &opts;
  ex.argument_lists = arg_lists;
  ex.num_devices = 1;
  ex.num_args = size_t(n_in);
  ex.output_lists = out_lists;
  ex.device_complete_events = done;
  if (take_error(api, api->PJRT_LoadedExecutable_Execute(&ex), err,
                 errn)) {
    return -1;
  }
  if (!await_event(api, done[0], err, errn)) return -1;
  for (int64_t i = 0; i < n_out; ++i) {
    out_bufs[i] = outs[size_t(i)];
  }
  return n_out;
}

}  // extern "C"
