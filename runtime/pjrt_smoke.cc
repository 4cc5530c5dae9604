// pjrt_smoke: TPU connectivity smoke test over the raw PJRT C API.
//
// The TPU-native equivalent of the reference's MPI cluster smoke test
// (/root/reference/mingpt/slurm/mpi_hello_world.c:1-19, the repo's only
// native source): where that program proved "the cluster schedules my ranks
// and they can say hello", this one proves "the PJRT plugin loads, the TPU
// client comes up, every chip is visible, a program compiles and runs, and
// the chips can talk" — the pre-flight check to run on a pod slice before
// launching training (SURVEY.md §2.1 item 1).
//
// Stages (each prints PASS/FAIL):
//   1. dlopen the PJRT plugin (.so from argv[1] or $PJRT_PLUGIN_PATH) and
//      resolve GetPjrtApi — the NCCL/c10d analogue is the PJRT runtime.
//   2. Create a client; print platform, process index, device inventory
//      (the hostname+rank printout of the MPI test).
//   3. Compile + run x+x on one device (H2D -> MXU -> D2H round trip).
//   4. If >1 addressable device: compile an N-replica stablehlo.all_reduce
//      and execute it across all devices — each replica contributes its
//      rank; every device must read back sum(0..N-1). This exercises the
//      ICI fabric the way DDP's first gradient all-reduce would.
//
// No protobuf dependency: the CompileOptionsProto is hand-encoded (field
// numbers from xla/pjrt/proto/compile_options.proto: executable_build_options
// = 3, .num_replicas = 4, .num_partitions = 5).
//
// Build: make (g++ -std=c++17 pjrt_smoke.cc -ldl). Run: ./pjrt_smoke [plugin.so]

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

const PJRT_Api* g_api = nullptr;

std::string ErrorMessage(PJRT_Error* err) {
  if (err == nullptr) return "";
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  return msg;
}

#define CHECK_OK(expr, what)                                          \
  do {                                                                \
    PJRT_Error* _err = (expr);                                        \
    if (_err != nullptr) {                                            \
      fprintf(stderr, "FAIL: %s: %s\n", what, ErrorMessage(_err).c_str()); \
      return 1;                                                       \
    }                                                                 \
  } while (0)

void AppendVarint(std::string* out, uint64_t v) {
  while (v >= 0x80) {
    out->push_back(static_cast<char>((v & 0x7F) | 0x80));
    v >>= 7;
  }
  out->push_back(static_cast<char>(v));
}

// CompileOptionsProto{ executable_build_options(3){ num_replicas(4)=n,
// num_partitions(5)=1 } }, hand-encoded.
std::string CompileOptionsBytes(int num_replicas) {
  std::string inner;
  inner.push_back(static_cast<char>((4 << 3) | 0));  // num_replicas varint
  AppendVarint(&inner, static_cast<uint64_t>(num_replicas));
  inner.push_back(static_cast<char>((5 << 3) | 0));  // num_partitions varint
  AppendVarint(&inner, 1);
  std::string outer;
  outer.push_back(static_cast<char>((3 << 3) | 2));  // executable_build_options
  AppendVarint(&outer, inner.size());
  outer += inner;
  return outer;
}

PJRT_Error* Compile(PJRT_Client* client, const std::string& mlir,
                    int num_replicas, PJRT_LoadedExecutable** out) {
  static const char kFormat[] = "mlir";
  std::string options = CompileOptionsBytes(num_replicas);
  PJRT_Program program;
  memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(mlir.data());
  program.code_size = mlir.size();
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = client;
  args.program = &program;
  args.compile_options = options.data();
  args.compile_options_size = options.size();
  PJRT_Error* err = g_api->PJRT_Client_Compile(&args);
  if (err == nullptr) *out = args.executable;
  return err;
}

// Host float -> device buffer (rank-0 f32).
PJRT_Error* ToDevice(PJRT_Client* client, PJRT_Device* device, float* value,
                     PJRT_Buffer** out) {
  PJRT_Client_BufferFromHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  args.client = client;
  args.data = value;
  args.type = PJRT_Buffer_Type_F32;
  args.dims = nullptr;
  args.num_dims = 0;
  args.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  args.device = device;
  PJRT_Error* err = g_api->PJRT_Client_BufferFromHostBuffer(&args);
  if (err != nullptr) return err;
  // wait until the host buffer is safe to reuse
  PJRT_Event_Await_Args await_args;
  memset(&await_args, 0, sizeof(await_args));
  await_args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  await_args.event = args.done_with_host_buffer;
  g_api->PJRT_Event_Await(&await_args);
  PJRT_Event_Destroy_Args evd;
  memset(&evd, 0, sizeof(evd));
  evd.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  evd.event = args.done_with_host_buffer;
  g_api->PJRT_Event_Destroy(&evd);
  *out = args.buffer;
  return nullptr;
}

PJRT_Error* ToHost(PJRT_Buffer* buffer, float* out) {
  PJRT_Buffer_ToHostBuffer_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  args.src = buffer;
  args.dst = out;
  args.dst_size = sizeof(float);
  PJRT_Error* err = g_api->PJRT_Buffer_ToHostBuffer(&args);
  if (err != nullptr) return err;
  PJRT_Event_Await_Args await_args;
  memset(&await_args, 0, sizeof(await_args));
  await_args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  await_args.event = args.event;
  PJRT_Error* aerr = g_api->PJRT_Event_Await(&await_args);
  PJRT_Event_Destroy_Args evd;
  memset(&evd, 0, sizeof(evd));
  evd.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  evd.event = args.event;
  g_api->PJRT_Event_Destroy(&evd);
  return aerr;
}

void DestroyBuffer(PJRT_Buffer* b) {
  PJRT_Buffer_Destroy_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  args.buffer = b;
  g_api->PJRT_Buffer_Destroy(&args);
}

// Execute a compiled executable with one scalar input per addressable device.
// Returns per-device scalar outputs.
PJRT_Error* ExecutePerDevice(PJRT_LoadedExecutable* exe,
                             std::vector<PJRT_Buffer*>& inputs,
                             std::vector<float>* outputs) {
  size_t n = inputs.size();
  std::vector<PJRT_Buffer* const*> arg_lists(n);
  std::vector<PJRT_Buffer*> args_flat = inputs;
  for (size_t i = 0; i < n; ++i) arg_lists[i] = &args_flat[i];

  std::vector<PJRT_Buffer**> out_lists(n);
  std::vector<PJRT_Buffer*> out_flat(n, nullptr);
  for (size_t i = 0; i < n; ++i) out_lists[i] = &out_flat[i];

  PJRT_ExecuteOptions opts;
  memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  args.executable = exe;
  args.options = &opts;
  args.argument_lists = arg_lists.data();
  args.num_devices = n;
  args.num_args = 1;
  args.output_lists = out_lists.data();
  PJRT_Error* err = g_api->PJRT_LoadedExecutable_Execute(&args);
  if (err != nullptr) return err;

  outputs->resize(n);
  for (size_t i = 0; i < n; ++i) {
    PJRT_Error* herr = ToHost(out_flat[i], &(*outputs)[i]);
    if (herr != nullptr) return herr;
    DestroyBuffer(out_flat[i]);
  }
  return nullptr;
}

std::string AllReduceMlir(int n) {
  std::string groups = "[[";
  for (int i = 0; i < n; ++i) {
    groups += std::to_string(i);
    if (i + 1 < n) groups += ", ";
  }
  groups += "]]";
  char buf[1024];
  snprintf(buf, sizeof(buf),
           "module attributes {mhlo.num_replicas = %d : i32, "
           "mhlo.num_partitions = 1 : i32} {\n"
           "  func.func @main(%%arg0: tensor<f32>) -> tensor<f32> {\n"
           "    %%0 = \"stablehlo.all_reduce\"(%%arg0) ({\n"
           "    ^bb0(%%a: tensor<f32>, %%b: tensor<f32>):\n"
           "      %%s = stablehlo.add %%a, %%b : tensor<f32>\n"
           "      stablehlo.return %%s : tensor<f32>\n"
           "    }) {replica_groups = dense<%s> : tensor<1x%dxi64>} : "
           "(tensor<f32>) -> tensor<f32>\n"
           "    return %%0 : tensor<f32>\n"
           "  }\n"
           "}\n",
           n, groups.c_str(), n);
  return buf;
}

}  // namespace

int main(int argc, char** argv) {
  // ---- stage 1: plugin ---------------------------------------------------
  const char* so_path = argc > 1 ? argv[1] : getenv("PJRT_PLUGIN_PATH");
  if (so_path == nullptr) {
    fprintf(stderr,
            "usage: pjrt_smoke <plugin.so>  (or set PJRT_PLUGIN_PATH, e.g. "
            "the libtpu.so of the installed libtpu package)\n");
    return 2;
  }
  void* handle = dlopen(so_path, RTLD_NOW | RTLD_LOCAL);
  if (handle == nullptr) {
    fprintf(stderr, "FAIL: dlopen(%s): %s\n", so_path, dlerror());
    return 1;
  }
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(handle, "GetPjrtApi"));
  if (get_api == nullptr) {
    fprintf(stderr, "FAIL: %s does not export GetPjrtApi\n", so_path);
    return 1;
  }
  g_api = get_api();
  printf("PASS: plugin %s (PJRT API v%d.%d)\n", so_path,
         g_api->pjrt_api_version.major_version,
         g_api->pjrt_api_version.minor_version);

  {
    PJRT_Plugin_Initialize_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    CHECK_OK(g_api->PJRT_Plugin_Initialize(&args), "PJRT_Plugin_Initialize");
  }

  // ---- stage 2: client + device inventory -------------------------------
  PJRT_Client* client = nullptr;
  {
    PJRT_Client_Create_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    args.create_options = nullptr;
    args.num_options = 0;
    CHECK_OK(g_api->PJRT_Client_Create(&args), "PJRT_Client_Create");
    client = args.client;
  }
  {
    PJRT_Client_PlatformName_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
    args.client = client;
    CHECK_OK(g_api->PJRT_Client_PlatformName(&args), "PlatformName");
    PJRT_Client_ProcessIndex_Args pargs;
    memset(&pargs, 0, sizeof(pargs));
    pargs.struct_size = PJRT_Client_ProcessIndex_Args_STRUCT_SIZE;
    pargs.client = client;
    CHECK_OK(g_api->PJRT_Client_ProcessIndex(&pargs), "ProcessIndex");
    printf("PASS: client up: platform=%.*s process_index=%d\n",
           static_cast<int>(args.platform_name_size), args.platform_name,
           pargs.process_index);
  }

  PJRT_Client_AddressableDevices_Args dev_args;
  memset(&dev_args, 0, sizeof(dev_args));
  dev_args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dev_args.client = client;
  CHECK_OK(g_api->PJRT_Client_AddressableDevices(&dev_args),
           "AddressableDevices");
  int n = static_cast<int>(dev_args.num_addressable_devices);
  printf("PASS: %d addressable device(s)\n", n);
  for (int i = 0; i < n; ++i) {
    PJRT_Device_GetDescription_Args gd;
    memset(&gd, 0, sizeof(gd));
    gd.struct_size = PJRT_Device_GetDescription_Args_STRUCT_SIZE;
    gd.device = dev_args.addressable_devices[i];
    CHECK_OK(g_api->PJRT_Device_GetDescription(&gd), "GetDescription");
    PJRT_DeviceDescription_DebugString_Args ds;
    memset(&ds, 0, sizeof(ds));
    ds.struct_size = PJRT_DeviceDescription_DebugString_Args_STRUCT_SIZE;
    ds.device_description = gd.device_description;
    CHECK_OK(g_api->PJRT_DeviceDescription_DebugString(&ds), "DebugString");
    printf("  device[%d]: %.*s\n", i, static_cast<int>(ds.debug_string_size),
           ds.debug_string);
  }
  if (n == 0) {
    fprintf(stderr, "FAIL: no addressable devices\n");
    return 1;
  }

  // ---- stage 3: single-device compile + execute -------------------------
  {
    const std::string mlir =
        "module {\n"
        "  func.func @main(%arg0: tensor<f32>) -> tensor<f32> {\n"
        "    %0 = stablehlo.add %arg0, %arg0 : tensor<f32>\n"
        "    return %0 : tensor<f32>\n"
        "  }\n"
        "}\n";
    PJRT_LoadedExecutable* exe = nullptr;
    CHECK_OK(Compile(client, mlir, 1, &exe), "compile x+x");
    float in = 21.0f;
    PJRT_Buffer* buf = nullptr;
    CHECK_OK(ToDevice(client, dev_args.addressable_devices[0], &in, &buf),
             "H2D");
    std::vector<PJRT_Buffer*> inputs = {buf};
    std::vector<float> outs;
    CHECK_OK(ExecutePerDevice(exe, inputs, &outs), "execute x+x");
    DestroyBuffer(buf);
    if (outs[0] != 42.0f) {
      fprintf(stderr, "FAIL: x+x: expected 42, got %f\n", outs[0]);
      return 1;
    }
    printf("PASS: single-device compile+execute (21+21=%g)\n", outs[0]);
  }

  // ---- stage 4: cross-chip all-reduce (the ICI hello-world) -------------
  if (n > 1) {
    PJRT_LoadedExecutable* exe = nullptr;
    CHECK_OK(Compile(client, AllReduceMlir(n), n, &exe), "compile all_reduce");
    std::vector<PJRT_Buffer*> inputs(n);
    std::vector<float> ranks(n);
    for (int i = 0; i < n; ++i) {
      ranks[i] = static_cast<float>(i);  // each replica contributes its rank
      CHECK_OK(ToDevice(client, dev_args.addressable_devices[i], &ranks[i],
                        &inputs[i]),
               "H2D rank");
    }
    std::vector<float> outs;
    CHECK_OK(ExecutePerDevice(exe, inputs, &outs), "execute all_reduce");
    float expect = static_cast<float>(n * (n - 1) / 2);
    for (int i = 0; i < n; ++i) {
      DestroyBuffer(inputs[i]);
      printf("  device[%d] psum(ranks) = %g (expect %g)\n", i, outs[i], expect);
      if (outs[i] != expect) {
        fprintf(stderr, "FAIL: all_reduce wrong on device %d\n", i);
        return 1;
      }
    }
    printf("PASS: %d-way cross-chip all-reduce\n", n);
  } else {
    printf("SKIP: all-reduce (single device visible)\n");
  }

  printf("OK: TPU slice is wired; safe to launch training\n");
  return 0;
}
