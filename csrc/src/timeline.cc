#include "hvd/timeline.h"

#include <chrono>

namespace hvd {

void Timeline::Initialize(const std::string& path, int rank) {
  if (initialized_.load()) return;
  file_ = std::fopen(path.c_str(), "w");
  if (file_ == nullptr) return;
  rank_ = rank;
  t0_ = std::chrono::steady_clock::now();
  std::fputs("[\n", file_);
  // the .so outlives init/shutdown cycles in one process: a second
  // session's first event must not open with the separator
  first_event_ = true;
  shutdown_.store(false);
  writer_ = std::thread([this] { WriterLoop(); });
  initialized_.store(true);
}

void Timeline::Shutdown() {
  if (!initialized_.load()) return;
  {
    std::lock_guard<std::mutex> lk(mu_);
    shutdown_.store(true);
  }
  cv_.notify_all();
  if (writer_.joinable()) writer_.join();
  std::fputs("\n]\n", file_);
  std::fclose(file_);
  file_ = nullptr;
  initialized_.store(false);
}

int64_t Timeline::NowUs() const {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now() - t0_)
      .count();
}

void Timeline::Enqueue(Event e) {
  if (!initialized_.load()) return;
  {
    std::lock_guard<std::mutex> lk(mu_);
    queue_.push_back(std::move(e));
  }
  cv_.notify_one();
}

void Timeline::NegotiateStart(const std::string& tensor, int request_type) {
  Enqueue({'B', tensor, "NEGOTIATE", "", NowUs()});
  (void)request_type;
}

void Timeline::NegotiateRankReady(const std::string& tensor, int rank) {
  Enqueue({'i', tensor, "rank " + std::to_string(rank) + " ready", "",
           NowUs()});
}

void Timeline::NegotiateEnd(const std::string& tensor) {
  Enqueue({'E', tensor, "NEGOTIATE", "", NowUs()});
}

void Timeline::Start(const std::string& tensor, const std::string& op_name) {
  Enqueue({'B', tensor, op_name, "", NowUs()});
}

void Timeline::ActivityStart(const std::string& tensor,
                             const std::string& activity) {
  Enqueue({'B', tensor, activity, "", NowUs()});
}

void Timeline::ActivityEnd(const std::string& tensor) {
  Enqueue({'E', tensor, "", "", NowUs()});
}

void Timeline::End(const std::string& tensor, int64_t bytes) {
  Enqueue({'E', tensor, "",
           bytes >= 0 ? "\"bytes\": " + std::to_string(bytes) : "", NowUs()});
}

void Timeline::MarkCycleStart() {
  Enqueue({'i', "cycle", "CYCLE_START", "", NowUs()});
}

void Timeline::MarkFusedLaunch(const std::string& op_name, size_t n_tensors,
                               size_t n_dtypes) {
  Enqueue({'i', "fusion",
           "FUSED_" + op_name + " x" + std::to_string(n_tensors) + " (" +
               std::to_string(n_dtypes) + " dtypes)",
           "", NowUs()});
}

void Timeline::WriterLoop() {
  while (true) {
    std::deque<Event> batch;
    {
      std::unique_lock<std::mutex> lk(mu_);
      cv_.wait(lk, [this] { return shutdown_.load() || !queue_.empty(); });
      batch.swap(queue_);
      if (batch.empty() && shutdown_.load()) return;
    }
    for (const auto& e : batch) {
      if (!first_event_) std::fputs(",\n", file_);
      first_event_ = false;
      // chrome tracing event: pid = rank, tid = tensor lane
      std::fprintf(file_,
                   "{\"ph\": \"%c\", \"pid\": %d, \"tid\": \"%s\", "
                   "\"ts\": %lld%s%s%s%s}",
                   e.phase, rank_, e.tid.c_str(),
                   static_cast<long long>(e.ts_us),
                   e.name.empty() ? "" : ", \"name\": \"",
                   e.name.empty() ? "" : e.name.c_str(),
                   e.name.empty() ? "" : "\"",
                   e.args.empty() ? "" : (", \"args\": {" + e.args + "}").c_str());
    }
    std::fflush(file_);
  }
}

}  // namespace hvd
