// extern "C" API + background negotiation loop
// (reference horovod/common/operations.cc:604-954: InitializeHorovodOnce,
// BackgroundThreadLoop, RunLoopOnce, EnqueueTensor*, horovod_* C API).
//
// The Python runtime registers an *execution callback*: each cycle the
// background thread computes the ResponseList and invokes the callback once
// per (possibly fused) Response with a compact description; Python launches
// the corresponding XLA collective on the registered device arrays and marks
// the per-tensor handles done. The C++ side never sees tensor data — the
// device data plane belongs to XLA (HBM), exactly the inversion of the
// reference where the core owns the fusion buffer memcpys.

#include <atomic>
#include <cctype>
#include <chrono>
#include <condition_variable>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <set>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "hvd/common.h"
#include "hvd/controller.h"
#include "hvd/parameter_manager.h"
#include "hvd/response_cache.h"
#include "hvd/stall_inspector.h"
#include "hvd/tcp_controller.h"
#include "hvd/tensor_queue.h"
#include "hvd/timeline.h"

namespace hvd {
namespace {

// Serialized Response handed to Python: see horovod_tpu/core.py for the
// mirrored decoding.
using ExecCallback = void (*)(const char* response_bytes, int len,
                              const int64_t* handles, int n_handles);
using LogCallback = void (*)(int level, const char* msg);

struct GlobalState {
  // reference HorovodGlobalState (global_state.h:42-122)
  std::atomic<bool> initialized{false};
  std::atomic<bool> shutdown_requested{false};
  // set once the background loop has left its last cycle, BEFORE it
  // aborts what is pending: an enqueue that comes later aborts its own
  std::atomic<bool> loop_exited{false};
  int rank = 0;
  int size = 1;
  double cycle_time_ms = 5.0;  // reference operations.cc:427
  TensorQueue tensor_queue;
  ResponseCache response_cache;
  StallInspector stall_inspector;
  Timeline timeline;
  ParameterManager parameter_manager;
  std::unique_ptr<Controller> controller;
  std::thread background;
  ExecCallback exec_cb = nullptr;
  LogCallback log_cb = nullptr;
  // hierarchical toggles as currently applied job-wide (-1 = never tuned):
  // attached to every exec-callback payload so the Python data plane flips
  // its strategy at the same cycle boundary on every rank
  std::atomic<int> hier_allreduce_applied{-1};
  std::atomic<int> hier_allgather_applied{-1};
  std::mutex init_mu_;
};

GlobalState g;

void Log(int level, const std::string& msg) {
  if (g.log_cb != nullptr) g.log_cb(level, msg.c_str());
}

int64_t ExecuteResponse(const Response& resp) {
  // collect python handles for every tensor in this (fused) response;
  // returns the bytes moved (autotune scoring signal)
  std::vector<int64_t> handles;
  int64_t bytes = 0;
  handles.reserve(resp.tensor_names.size());
  if (resp.tensor_names.size() > 1) {
    std::set<int32_t> dtypes(resp.tensor_dtypes.begin(),
                             resp.tensor_dtypes.end());
    g.timeline.MarkFusedLaunch(Response::TypeName(resp.response_type),
                               resp.tensor_names.size(),
                               dtypes.empty() ? 1 : dtypes.size());
  }
  for (const auto& name : resp.tensor_names) {
    TensorTableEntry e;
    if (g.tensor_queue.PopEntry(name, &e)) {
      handles.push_back(e.handle);
      bytes += e.meta.tensor_shape.num_elements() *
               DataTypeSize(static_cast<DataType>(e.meta.tensor_type));
      g.timeline.NegotiateEnd(name);
      g.timeline.Start(name, Response::TypeName(resp.response_type));
    } else {
      handles.push_back(-1);
    }
  }
  if (g.exec_cb != nullptr) {
    std::string payload;
    SerializeResponseList(
        [&] {
          ResponseList l;
          l.responses.push_back(resp);
          l.tuned_hier_allreduce = g.hier_allreduce_applied.load();
          l.tuned_hier_allgather = g.hier_allgather_applied.load();
          return l;
        }(),
        &payload);
    g.exec_cb(payload.data(), static_cast<int>(payload.size()),
              handles.data(), static_cast<int>(handles.size()));
  }
  for (const auto& name : resp.tensor_names) {
    g.timeline.End(name, -1);
  }
  return bytes;
}

void RunLoopOnce(std::chrono::steady_clock::time_point& last_cycle) {
  // sleep out the remainder of the cycle (reference operations.cc:550-560)
  auto target = last_cycle + std::chrono::duration_cast<
                                 std::chrono::steady_clock::duration>(
                                 std::chrono::duration<double, std::milli>(
                                     g.cycle_time_ms));
  std::this_thread::sleep_until(target);
  last_cycle = std::chrono::steady_clock::now();
  g.timeline.MarkCycleStart();

  ResponseList list =
      g.controller->ComputeResponseList(g.shutdown_requested.load());
  // apply coordinator-tuned parameters (no-op unless autotuning; identical
  // on the coordinator, the broadcast value on workers)
  if (list.tuned_cycle_time_ms > 0) g.cycle_time_ms = list.tuned_cycle_time_ms;
  if (list.tuned_fusion_threshold >= 0) {
    g.controller->SetFusionThresholdBytes(list.tuned_fusion_threshold);
  }
  if (list.tuned_cache_enabled >= 0) {
    if (std::getenv("HVD_DEBUG_CACHE") != nullptr &&
        g.controller->cache_enabled() != (list.tuned_cache_enabled != 0)) {
      std::fprintf(stderr, "[hvddbg r%d] cache toggle -> %d\n", g.rank,
                   (int)(list.tuned_cache_enabled != 0));
    }
    g.controller->SetCacheEnabled(list.tuned_cache_enabled != 0);
  }
  if (list.tuned_hier_allreduce >= 0) {
    g.hier_allreduce_applied.store(list.tuned_hier_allreduce != 0 ? 1 : 0);
  }
  if (list.tuned_hier_allgather >= 0) {
    g.hier_allgather_applied.store(list.tuned_hier_allgather != 0 ? 1 : 0);
  }
  int64_t bytes = 0;
  for (const auto& resp : list.responses) {
    bytes += ExecuteResponse(resp);
  }
  if (g.rank == 0 && g.parameter_manager.IsAutoTuning()) {
    g.parameter_manager.Update(bytes);
    // Do NOT apply the new choice here: tuned values ride the next cycle's
    // ResponseList, which every rank (coordinator included) applies at the
    // same point above — applying immediately would let rank 0 bin-pack one
    // cycle with a different fusion threshold than the workers and launch
    // mismatched grouped collectives (cross-process deadlock).
    g.controller->SetAutotunedParams(
        g.parameter_manager.cycle_time_ms(),
        g.parameter_manager.fusion_threshold(),
        g.parameter_manager.cache_enabled() ? 1 : 0,
        g.parameter_manager.hier_allreduce() ? 1 : 0,
        g.parameter_manager.hier_allgather() ? 1 : 0);
  }
  if (list.shutdown) {
    g.shutdown_requested.store(true);
  }
}

// Abort everything still pending with the shutdown error (reference
// operations.cc:526-532). Called by the background loop as it exits and by
// an enqueue that finds the loop gone: a handle is drained by one of them.
void AbortPending() {
  auto handles = g.tensor_queue.DrainAllHandles();
  if (g.exec_cb != nullptr && !handles.empty()) {
    ResponseList l;
    Response r;
    r.response_type = Response::ERROR;
    std::string cause =
        g.controller != nullptr ? g.controller->lost_peer_detail() : "";
    r.error_message =
        cause.empty()
            ? "Horovod background loop shut down; pending collective aborted."
            : "Horovod background loop shut down (" + cause +
                  "); pending collective aborted.";
    l.responses.push_back(r);
    l.shutdown = true;
    std::string payload;
    SerializeResponseList(l, &payload);
    g.exec_cb(payload.data(), static_cast<int>(payload.size()),
              handles.data(), static_cast<int>(handles.size()));
  }
}

void BackgroundThreadLoop() {
  auto last_cycle = std::chrono::steady_clock::now();
  while (!g.shutdown_requested.load()) {
    RunLoopOnce(last_cycle);
  }
  g.loop_exited.store(true);
  AbortPending();
  g.timeline.Shutdown();
}

}  // namespace
}  // namespace hvd

extern "C" {

// init for single-process (local controller) or multi-process (tcp).
// coordinator_host may be null/empty for local mode.
int hvd_core_init(int rank, int size, const char* coordinator_host,
                  int coordinator_port, double cycle_time_ms,
                  int64_t fusion_threshold_bytes, int cache_capacity,
                  double stall_warning_s, double stall_shutdown_s,
                  const char* timeline_path) {
  using namespace hvd;
  std::lock_guard<std::mutex> lk(g.init_mu_);
  if (g.initialized.load()) return 0;
  g.rank = rank;
  g.size = size;
  g.cycle_time_ms = cycle_time_ms > 0 ? cycle_time_ms : 5.0;
  g.shutdown_requested.store(false);
  g.loop_exited.store(false);
  // the .so (and its globals) outlives init/shutdown cycles in one
  // process: a previous session's tuned toggles must not leak into a
  // fresh session as "already applied"
  g.hier_allreduce_applied.store(-1);
  g.hier_allgather_applied.store(-1);
  g.response_cache.set_capacity(
      cache_capacity >= 0 ? static_cast<size_t>(cache_capacity) : 1024);
  g.stall_inspector.set_warning_seconds(stall_warning_s > 0 ? stall_warning_s
                                                            : 60.0);
  g.stall_inspector.set_shutdown_seconds(stall_shutdown_s);
  g.stall_inspector.set_log_fn(
      [](const std::string& m) { Log(2, m); });
  if (timeline_path != nullptr && timeline_path[0] != '\0' && rank == 0) {
    g.timeline.Initialize(timeline_path, rank);
  }
  // autotune knobs from env (reference operations.cc:470-500 reads
  // HOROVOD_AUTOTUNE / HOROVOD_AUTOTUNE_LOG / warmup+sample counts)
  {
    const char* at = std::getenv("HOROVOD_AUTOTUNE");
    bool autotune = at != nullptr && at[0] != '\0' && std::strcmp(at, "0") != 0;
    auto env_int = [](const char* name, int dflt) {
      const char* v = std::getenv(name);
      return (v != nullptr && v[0] != '\0') ? std::atoi(v) : dflt;
    };
    auto env_f = [](const char* name, double dflt) {
      const char* v = std::getenv(name);
      return (v != nullptr && v[0] != '\0') ? std::atof(v) : dflt;
    };
    const char* log = std::getenv("HOROVOD_AUTOTUNE_LOG");
    auto env_on = [](const char* name) {
      // accept the same spellings as the Python data plane's _env_on
      // (ops/hierarchical.py): 1/true/yes/on, case-insensitive
      const char* v = std::getenv(name);
      if (v == nullptr || v[0] == '\0') return false;
      std::string s(v);
      for (auto& c : s) c = static_cast<char>(std::tolower(c));
      return s == "1" || s == "true" || s == "yes" || s == "on";
    };
    g.parameter_manager.Initialize(
        g.cycle_time_ms,
        fusion_threshold_bytes >= 0 ? fusion_threshold_bytes
                                    : 64ll * 1024 * 1024,
        env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3),
        env_int("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10),
        env_int("HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20),
        env_f("HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8),
        (rank == 0 && log != nullptr) ? log : "",
        // seed the search from the user's explicit strategy choice
        // (reference operations.cc:455-469 reads the same env pair)
        env_on("HOROVOD_HIERARCHICAL_ALLREDUCE"),
        env_on("HOROVOD_HIERARCHICAL_ALLGATHER"));
    // only the coordinator runs the search (workers apply broadcast values),
    // so only its status surface reports "tuning"
    g.parameter_manager.SetAutoTuning(autotune && rank == 0);
  }
  if (size > 1 && coordinator_host != nullptr && coordinator_host[0] != '\0') {
    auto* tcp = new TcpController(rank, size, coordinator_host,
                                  coordinator_port, g.tensor_queue,
                                  g.response_cache, g.stall_inspector);
    Status s = tcp->Initialize();
    if (!s.ok()) {
      Log(3, "controller init failed: " + s.reason());
      delete tcp;
      return -1;
    }
    g.controller.reset(tcp);
  } else {
    g.controller.reset(new LocalController(rank, size, g.tensor_queue,
                                           g.response_cache,
                                           g.stall_inspector));
  }
  if (fusion_threshold_bytes >= 0) {
    g.controller->SetFusionThresholdBytes(fusion_threshold_bytes);
  }
  g.background = std::thread(BackgroundThreadLoop);
  g.initialized.store(true);
  return 0;
}

void hvd_core_set_exec_callback(void (*cb)(const char*, int, const int64_t*,
                                           int)) {
  hvd::g.exec_cb = cb;
}

void hvd_core_set_log_callback(void (*cb)(int, const char*)) {
  hvd::g.log_cb = cb;
}

int hvd_core_enqueue(const char* name, int request_type, int dtype,
                     const int64_t* dims, int ndim, int root_rank,
                     int reduce_op, double prescale, double postscale,
                     int64_t handle, const char* axis_name) {
  using namespace hvd;
  if (!g.initialized.load()) return -1;
  TensorTableEntry e;
  e.handle = handle;
  e.meta.request_rank = g.rank;
  e.meta.request_type = request_type;
  e.meta.tensor_type = dtype;
  e.meta.root_rank = root_rank;
  e.meta.reduce_op = reduce_op;
  e.meta.prescale_factor = prescale;
  e.meta.postscale_factor = postscale;
  e.meta.tensor_name = name;
  e.meta.axis_name = axis_name != nullptr ? axis_name : "";
  std::vector<int64_t> d(dims, dims + ndim);
  e.meta.tensor_shape = TensorShape(std::move(d));
  g.timeline.NegotiateStart(e.meta.tensor_name, request_type);
  Status s = g.tensor_queue.AddToTensorQueue(e);
  if (!s.ok()) return 1;  // duplicate name
  // The loop can end by itself (a lost peer, the stall shutdown) while the
  // caller still holds a live core: nothing would ever drain this entry and
  // its waiter would hang to its own timeout. The flag is set before the
  // loop's drain, so either that drain took the entry or this one does.
  if (g.loop_exited.load()) AbortPending();
  return 0;
}

int hvd_core_pending(void) {
  return static_cast<int>(hvd::g.tensor_queue.pending_count());
}

void hvd_core_shutdown(void) {
  using namespace hvd;
  std::lock_guard<std::mutex> lk(g.init_mu_);
  if (!g.initialized.load()) return;
  g.shutdown_requested.store(true);
  if (g.background.joinable()) g.background.join();
  g.controller.reset();
  g.response_cache.clear();
  g.initialized.store(false);
}

int hvd_core_initialized(void) { return hvd::g.initialized.load() ? 1 : 0; }
int hvd_core_rank(void) { return hvd::g.rank; }
int hvd_core_size(void) { return hvd::g.size; }

double hvd_core_cycle_time_ms(void) { return hvd::g.cycle_time_ms; }
void hvd_core_set_cycle_time_ms(double ms) {
  if (ms > 0) hvd::g.cycle_time_ms = ms;
}
int64_t hvd_core_fusion_threshold(void) {
  return hvd::g.controller ? hvd::g.controller->fusion_threshold_bytes() : -1;
}

// autotuner observability (tests + Python-side status surface)
int hvd_core_autotune_active(void) {
  return hvd::g.parameter_manager.IsAutoTuning() ? 1 : 0;
}
int hvd_core_autotune_samples(void) {
  return hvd::g.parameter_manager.num_samples();
}
double hvd_core_autotune_best_score(void) {
  return hvd::g.parameter_manager.best_score();
}
int hvd_core_cache_enabled(void) {
  return hvd::g.controller && hvd::g.controller->cache_enabled() ? 1 : 0;
}
void hvd_core_set_cache_enabled(int enabled) {
  if (hvd::g.controller) hvd::g.controller->SetCacheEnabled(enabled != 0);
}
void hvd_core_set_fusion_threshold(int64_t bytes) {
  if (hvd::g.controller && bytes >= 0) {
    hvd::g.controller->SetFusionThresholdBytes(bytes);
  }
}

uint64_t hvd_core_cache_hit_count(void) {
  return hvd::g.controller ? hvd::g.controller->cache_hit_count() : 0;
}

// hierarchical toggles as applied job-wide this cycle (-1 = never tuned)
int hvd_core_hier_allreduce(void) {
  return hvd::g.hier_allreduce_applied.load();
}
int hvd_core_hier_allgather(void) {
  return hvd::g.hier_allgather_applied.load();
}

// Coordinator-side manual injection into the tuned broadcast: the values
// ride the NEXT cycle's ResponseList and every rank (coordinator included)
// applies them at the same cycle boundary — the collectively-safe way to
// retune mid-run without HOROVOD_AUTOTUNE (also the np=2 toggle test's
// entry point). No-op on workers.
void hvd_core_set_autotuned_params(double cycle_ms, int64_t fusion_bytes,
                                   int cache_enabled, int hier_allreduce,
                                   int hier_allgather) {
  using namespace hvd;
  if (!g.controller || g.rank != 0) return;
  g.controller->SetAutotunedParams(cycle_ms, fusion_bytes, cache_enabled,
                                   hier_allreduce, hier_allgather);
}

}  // extern "C"
