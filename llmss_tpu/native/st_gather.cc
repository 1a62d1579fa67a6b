// Native safetensors gather: multi-threaded strided pread into caller
// buffers.
//
// TPU-native replacement for the I/O half of the reference's lazy sharded
// loader (utils/weights.py:72-95 reads each rank's slice through the
// safetensors Python binding, one GIL-bound call per tensor). Weight loading
// is cold-start critical, and a TP shard read is
// just a strided byte gather — so the data plane is plain C++: one pread(2)
// per contiguous run, fanned out over a thread pool, no Python in the loop.
//
// A "segment" is one logical read: n_chunks runs of chunk_bytes each,
// file_stride apart, packed contiguously into dst. That expresses
//   - a full tensor / dim-0 shard   (n_chunks = 1)
//   - a dim-1 / column shard        (n_chunks = rows, stride = row_bytes)
//   - any 2D rectangle              (ditto, offset shifted)
// Chunks are flattened into one global work list so many small segments
// (e.g. every layer's slice of a stacked load) share the pool evenly.
//
// Exposed as a tiny C ABI for ctypes; no Python.h dependency.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <fcntl.h>
#include <thread>
#include <unistd.h>
#include <vector>

namespace {

struct Chunk {
  int64_t file_offset;
  int64_t bytes;
  unsigned char* dst;
};

int read_chunk(int fd, const Chunk& c) {
  int64_t done = 0;
  while (done < c.bytes) {
    ssize_t n = pread(fd, c.dst + done, static_cast<size_t>(c.bytes - done),
                      static_cast<off_t>(c.file_offset + done));
    if (n < 0) {
      if (errno == EINTR) continue;
      return errno ? errno : -1;
    }
    if (n == 0) return -2;  // unexpected EOF: header/offsets disagree
    done += n;
  }
  return 0;
}

}  // namespace

extern "C" {

// Returns 0 on success, a positive errno, or a negative internal code.
int st_gather(const char* path, int64_t n_segments,
              const int64_t* file_offsets, const int64_t* chunk_bytes,
              const int64_t* n_chunks, const int64_t* file_strides,
              unsigned char** dsts, int n_threads) {
  int fd = open(path, O_RDONLY);
  if (fd < 0) return errno ? errno : -1;

  std::vector<Chunk> chunks;
  for (int64_t s = 0; s < n_segments; ++s) {
    unsigned char* dst = dsts[s];
    for (int64_t j = 0; j < n_chunks[s]; ++j) {
      if (chunk_bytes[s] == 0) continue;
      chunks.push_back(Chunk{file_offsets[s] + j * file_strides[s],
                             chunk_bytes[s], dst + j * chunk_bytes[s]});
    }
  }

  if (n_threads < 1) n_threads = 1;
  size_t pool = std::min<size_t>(static_cast<size_t>(n_threads),
                                 chunks.size() ? chunks.size() : 1);
  std::atomic<size_t> next{0};
  std::atomic<int> err{0};

  auto worker = [&]() {
    for (;;) {
      size_t i = next.fetch_add(1);
      if (i >= chunks.size() || err.load()) break;
      int rc = read_chunk(fd, chunks[i]);
      if (rc) err.store(rc);
    }
  };

  if (pool <= 1) {
    worker();
  } else {
    std::vector<std::thread> threads;
    threads.reserve(pool);
    for (size_t t = 0; t < pool; ++t) threads.emplace_back(worker);
    for (auto& t : threads) t.join();
  }

  close(fd);
  return err.load();
}

}  // extern "C"
