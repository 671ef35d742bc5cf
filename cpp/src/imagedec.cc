/*
 * Native image decode + augment pipeline.
 *
 * TPU-native analogue of the reference's ImageRecordIOParser2 OMP decode loop
 * (src/io/iter_image_recordio_2.cc:138-171) + PrefetcherIter
 * (src/io/iter_prefetcher.h:47): worker threads pull raw records from the
 * sharded prefetching RecordIO reader (recordio.cc), decode JPEG (libjpeg)
 * or the repo's RAW0 blobs, resize/crop/mirror, and assemble uint8 NHWC
 * batches into a bounded queue.
 *
 * Design choices for the TPU host:
 * - output is uint8 NHWC + float labels: normalization/transpose runs on the
 *   *device* inside the jitted step (HBM-friendly: 1 byte/px across the host
 *   link instead of 4).
 * - each worker assembles whole batches independently (no per-image slot
 *   coordination); batch order across workers is nondeterministic, which is
 *   fine for training and keeps the hot path lock-free outside record fetch.
 * - JPEG decode uses libjpeg scale_denom to decode at the smallest scale
 *   >= resize target before the bilinear resize (the reference relies on
 *   OpenCV for the same trick).
 */
#include "../include/mxtpu.h"

#include "common.h"

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <csetjmp>
#include <cstdio>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <vector>

#ifdef MXTPU_HAVE_LIBJPEG
#include <jpeglib.h>
#endif

#include "imageutil.h"

namespace mxtpu {
namespace img {

// ------------------------------------------------------------------ decode

#ifdef MXTPU_HAVE_LIBJPEG
struct JpegErr {
  jpeg_error_mgr mgr;
  jmp_buf jmp;
};

void JpegErrExit(j_common_ptr cinfo) {
  auto *err = reinterpret_cast<JpegErr *>(cinfo->err);
  longjmp(err->jmp, 1);
}

// Decodes JPEG bytes to tightly-packed RGB; returns false on corrupt input.
// row_scratch is caller-owned so the error longjmp never skips a local
// vector's destructor.
bool DecodeJpeg(const uint8_t *data, size_t len, int min_side,
                std::vector<uint8_t> *out, std::vector<uint8_t> *row_scratch,
                int *h, int *w) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = JpegErrExit;
  if (setjmp(jerr.jmp)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, data, len);
  if (jpeg_read_header(&cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  cinfo.out_color_space = JCS_RGB;
  // decode at the smallest 1/den scale whose short side still covers the
  // resize target
  if (min_side > 0) {
    int short_side = std::min<int>(cinfo.image_width, cinfo.image_height);
    int den = 1;
    while (den < 8 && short_side / (den * 2) >= min_side) den *= 2;
    cinfo.scale_num = 1;
    cinfo.scale_denom = den;
  }
  jpeg_start_decompress(&cinfo);
  *w = cinfo.output_width;
  *h = cinfo.output_height;
  out->resize(static_cast<size_t>(*h) * *w * 3);
  row_scratch->resize(static_cast<size_t>(*w) * cinfo.output_components);
  std::vector<uint8_t> &row = *row_scratch;
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t *rp = row.data();
    jpeg_read_scanlines(&cinfo, &rp, 1);
    uint8_t *dst = out->data() + static_cast<size_t>(cinfo.output_scanline - 1) * *w * 3;
    if (cinfo.output_components == 3) {
      std::memcpy(dst, row.data(), static_cast<size_t>(*w) * 3);
    } else {  // grayscale: broadcast
      for (int x = 0; x < *w; ++x) {
        dst[3 * x] = dst[3 * x + 1] = dst[3 * x + 2] = row[x];
      }
    }
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}
#else
// Built without libjpeg: JPEG records are reported as undecodable (skipped);
// RAW0 blobs still work so the core runtime never disappears. Diagnose once
// instead of silently yielding an empty epoch on a JPEG dataset.
bool DecodeJpeg(const uint8_t *, size_t, int, std::vector<uint8_t> *,
                std::vector<uint8_t> *, int *, int *) {
  static std::atomic<bool> warned{false};
  if (!warned.exchange(true)) {
    std::fprintf(stderr,
                 "[mxtpu] libmxtpu.so was built without libjpeg; JPEG "
                 "records are skipped (rebuild with libjpeg-dev for JPEG "
                 "datasets)\n");
  }
  return false;
}
#endif

// The repo's PIL-free fallback blob: "RAW0" + ndim + int32 shape + uint8 data.
bool DecodeRaw0(const uint8_t *data, size_t len, std::vector<uint8_t> *out,
                int *h, int *w) {
  if (len < 8 || std::memcmp(data, "RAW0", 4) != 0) return false;
  uint32_t ndim;
  std::memcpy(&ndim, data + 4, 4);
  if (ndim < 2 || ndim > 3 || len < 8 + 4 * ndim) return false;
  int32_t shape[3] = {0, 0, 1};
  std::memcpy(shape, data + 8, 4 * ndim);
  size_t need = static_cast<size_t>(shape[0]) * shape[1] * shape[2];
  const uint8_t *px = data + 8 + 4 * ndim;
  if (len - (8 + 4 * ndim) < need) return false;
  *h = shape[0];
  *w = shape[1];
  int c = ndim == 3 ? shape[2] : 1;
  out->resize(static_cast<size_t>(*h) * *w * 3);
  if (c == 3) {
    std::memcpy(out->data(), px, need);
  } else {  // grayscale
    for (size_t i = 0; i < static_cast<size_t>(*h) * *w; ++i) {
      (*out)[3 * i] = (*out)[3 * i + 1] = (*out)[3 * i + 2] = px[i * c];
    }
  }
  return true;
}

// Bilinear resize RGB HWC uint8.
void ResizeBilinear(const uint8_t *src, int sh, int sw, uint8_t *dst, int dh,
                    int dw) {
  if (sh == dh && sw == dw) {
    std::memcpy(dst, src, static_cast<size_t>(dh) * dw * 3);
    return;
  }
  const float ys = static_cast<float>(sh) / dh;
  const float xs = static_cast<float>(sw) / dw;
  for (int y = 0; y < dh; ++y) {
    float fy = (y + 0.5f) * ys - 0.5f;
    int y0 = std::max(0, static_cast<int>(fy));
    int y1 = std::min(sh - 1, y0 + 1);
    float wy = fy - y0;
    if (wy < 0) wy = 0;
    for (int x = 0; x < dw; ++x) {
      float fx = (x + 0.5f) * xs - 0.5f;
      int x0 = std::max(0, static_cast<int>(fx));
      int x1 = std::min(sw - 1, x0 + 1);
      float wx = fx - x0;
      if (wx < 0) wx = 0;
      const uint8_t *p00 = src + (static_cast<size_t>(y0) * sw + x0) * 3;
      const uint8_t *p01 = src + (static_cast<size_t>(y0) * sw + x1) * 3;
      const uint8_t *p10 = src + (static_cast<size_t>(y1) * sw + x0) * 3;
      const uint8_t *p11 = src + (static_cast<size_t>(y1) * sw + x1) * 3;
      uint8_t *d = dst + (static_cast<size_t>(y) * dw + x) * 3;
      for (int ch = 0; ch < 3; ++ch) {
        float v = (1 - wy) * ((1 - wx) * p00[ch] + wx * p01[ch]) +
                  wy * ((1 - wx) * p10[ch] + wx * p11[ch]);
        d[ch] = static_cast<uint8_t>(v + 0.5f);
      }
    }
  }
}

}  // namespace img
}  // namespace mxtpu

namespace {

using mxtpu::img::DecodeJpeg;
using mxtpu::img::DecodeRaw0;
using mxtpu::img::ResizeBilinear;

// ------------------------------------------------------------------ pipeline

struct ImgBatch {
  std::vector<uint8_t> data;   // B*H*W*3, NHWC
  std::vector<float> labels;   // B*label_width
  int count = 0;
};

struct PipeConfig {
  int batch_size, out_h, out_w, resize_px;
  int num_threads, queue_depth;
  int rand_crop, rand_mirror, shuffle;
  int label_width;
  uint64_t seed;
  // batches every shard must emit per epoch (ceil(max_shard_size / B));
  // shards short on records pad with count=0 batches so synchronized
  // data-parallel hosts step the same number of times (-1 = no target)
  int64_t target_batches = -1;
};

class ImagePipeline {
 public:
  ImagePipeline(void *rec_handle, const PipeConfig &cfg)
      : rec_(rec_handle), cfg_(cfg) {
    Start();
  }

  ~ImagePipeline() {
    Stop();
    mxtpu_rec_close(rec_);
  }

  // 1 = batch, 0 = end of epoch, -1 = error
  int Next(ImgBatch **out) {
    std::unique_lock<std::mutex> lk(mu_);
    cv_pop_.wait(lk, [&] { return !queue_.empty() || workers_done_ == cfg_.num_threads; });
    if (!queue_.empty()) {
      *out = queue_.front().release();
      queue_.pop_front();
      cv_push_.notify_all();
      return 1;
    }
    if (!error_.empty()) {
      mxtpu::SetError(error_);
      return -1;
    }
    *out = nullptr;
    return 0;
  }

  int Reset() {
    Stop();
    if (mxtpu_rec_reset(rec_)) return -1;
    {
      std::lock_guard<std::mutex> lk(mu_);
      queue_.clear();
      workers_done_ = 0;
      real_done_ = 0;
      error_.clear();
      pending_.clear();
      stream_end_ = false;
      emitted_.store(0, std::memory_order_relaxed);
      tmpl_.reset();
      ++epoch_;  // augmentation randomness must differ across epochs
    }
    Start();
    return 0;
  }

 private:
  void Start() {
    stop_ = false;
    for (int i = 0; i < cfg_.num_threads; ++i) {
      workers_.emplace_back([this, i] { WorkerLoop(i); });
    }
  }

  void Stop() {
    {
      std::lock_guard<std::mutex> lk(mu_);
      stop_ = true;
    }
    cv_push_.notify_all();
    cv_rec_.notify_all();
    for (auto &t : workers_) t.join();
    workers_.clear();
  }

  // Fetch up to `n` raw records from the shared reader in one critical
  // section, so a worker always owns a whole batch's worth and small files
  // never strand partial batches across workers.
  size_t FetchChunk(size_t n, std::vector<std::string> *out) {
    std::lock_guard<std::mutex> lk(rec_mu_);
    while (pending_.size() < n && !stream_end_) {
      void *batch = nullptr;
      int count = 0;
      if (mxtpu_rec_next_batch(rec_, &batch, &count)) {
        stream_end_ = true;
        std::lock_guard<std::mutex> elk(mu_);
        if (error_.empty()) error_ = mxtpu_last_error();
        break;
      }
      if (batch == nullptr) {
        stream_end_ = true;
        break;
      }
      for (int i = 0; i < count; ++i) {
        const uint8_t *data;
        uint64_t len;
        mxtpu_rec_get(batch, i, &data, &len);
        pending_.emplace_back(reinterpret_cast<const char *>(data), len);
      }
      mxtpu_rec_free_batch(batch);
    }
    size_t take = std::min(n, pending_.size());
    for (size_t i = 0; i < take; ++i) {
      out->push_back(std::move(pending_.front()));
      pending_.pop_front();
    }
    return take;
  }

  void WorkerLoop(int worker_id) {
    // distinct stream per worker AND per epoch
    std::mt19937 rng(static_cast<uint32_t>(cfg_.seed + worker_id +
                                           9973u * epoch_));
    const int B = cfg_.batch_size;
    const int H = cfg_.out_h, W = cfg_.out_w;
    // shuffle window: workers draw several batches of records at once and
    // permute them (the reference shuffles decode chunks the same way)
    const int window = cfg_.shuffle ? 4 * B : B;
    std::vector<uint8_t> decoded, resized, row_scratch;
    std::vector<std::string> chunk;
    size_t chunk_pos = 0;
    bool exhausted = false;
    while (!exhausted) {
      if (chunk_pos >= chunk.size()) {
        chunk.clear();
        chunk_pos = 0;
        if (FetchChunk(window, &chunk) == 0) break;
        if (cfg_.shuffle) {
          std::shuffle(chunk.begin(), chunk.end(), rng);
        }
      }
      auto batch = std::make_unique<ImgBatch>();
      batch->data.resize(static_cast<size_t>(B) * H * W * 3);
      batch->labels.assign(static_cast<size_t>(B) * cfg_.label_width, 0.f);
      int filled = 0;
      while (filled < B) {
        if (stop_.load(std::memory_order_relaxed)) return;
        if (chunk_pos >= chunk.size()) {
          chunk.clear();
          chunk_pos = 0;
          if (FetchChunk(window, &chunk) == 0) {
            exhausted = true;
            break;
          }
          if (cfg_.shuffle) {
            std::shuffle(chunk.begin(), chunk.end(), rng);
          }
        }
        if (DecodeOne(chunk[chunk_pos++], rng, &decoded, &resized,
                      &row_scratch,
                      batch->data.data() +
                          static_cast<size_t>(filled) * H * W * 3,
                      batch->labels.data() +
                          static_cast<size_t>(filled) * cfg_.label_width)) {
          ++filled;
        }
        // corrupt records are skipped (the reference logs-and-skips too)
      }
      if (filled == 0) break;
      if (filled < B) {
        // pad the trailing batch by repeating its own rows (reference
        // DataBatch.pad semantics); count records the real sample count so
        // every shard emits the same ceil(n/B) batches
        for (int i = filled; i < B; ++i) {
          int src = i % filled;
          std::memcpy(batch->data.data() + static_cast<size_t>(i) * H * W * 3,
                      batch->data.data() + static_cast<size_t>(src) * H * W * 3,
                      static_cast<size_t>(H) * W * 3);
          std::memcpy(
              batch->labels.data() + static_cast<size_t>(i) * cfg_.label_width,
              batch->labels.data() + static_cast<size_t>(src) * cfg_.label_width,
              sizeof(float) * cfg_.label_width);
        }
      }
      batch->count = filled;
      std::unique_lock<std::mutex> lk(mu_);
      if (!tmpl_) tmpl_ = std::make_unique<ImgBatch>(*batch);
      cv_push_.wait(lk, [&] {
        return stop_.load(std::memory_order_relaxed) ||
               static_cast<int>(queue_.size()) < cfg_.queue_depth;
      });
      if (stop_.load(std::memory_order_relaxed)) return;
      queue_.push_back(std::move(batch));
      emitted_.fetch_add(1, std::memory_order_relaxed);
      cv_pop_.notify_one();
    }
    // equal steps across shards: emit count=0 pad batches until this shard
    // reaches the per-epoch target (consumers treat count as the real
    // sample count, so metrics skip the padding).  Only the LAST worker to
    // run out of records pads: emitted_ counts a real batch when it is
    // pushed, so a worker that finished early would otherwise see a count
    // that is still short of the target while another worker is decoding
    // the final real batch, and pad one batch too many.
    bool last_worker;
    {
      std::lock_guard<std::mutex> lk(mu_);
      last_worker = (++real_done_ == cfg_.num_threads);
    }
    while (last_worker && cfg_.target_batches >= 0) {
      int64_t cur = emitted_.load(std::memory_order_relaxed);
      if (cur >= cfg_.target_batches ||
          stop_.load(std::memory_order_relaxed))
        break;
      if (!emitted_.compare_exchange_strong(cur, cur + 1)) continue;
      auto pad = std::make_unique<ImgBatch>();
      {
        std::unique_lock<std::mutex> lk(mu_);
        if (tmpl_) {
          *pad = *tmpl_;
        } else {  // shard saw zero records: zero-filled frame
          pad->data.assign(static_cast<size_t>(B) * H * W * 3, 0);
          pad->labels.assign(static_cast<size_t>(B) * cfg_.label_width, 0.f);
        }
        pad->count = 0;
        cv_push_.wait(lk, [&] {
          return stop_.load(std::memory_order_relaxed) ||
                 static_cast<int>(queue_.size()) < cfg_.queue_depth;
        });
        if (stop_.load(std::memory_order_relaxed)) return;
        queue_.push_back(std::move(pad));
        cv_pop_.notify_one();
      }
    }
    std::lock_guard<std::mutex> lk(mu_);
    ++workers_done_;
    cv_pop_.notify_all();
  }

  bool DecodeOne(const std::string &rec, std::mt19937 &rng,
                 std::vector<uint8_t> *decoded, std::vector<uint8_t> *resized,
                 std::vector<uint8_t> *row_scratch, uint8_t *out_px,
                 float *out_label) {
    // IRHeader: uint32 flag, float label, uint64 id, uint64 id2 (24 bytes)
    if (rec.size() < 24) return false;
    const uint8_t *p = reinterpret_cast<const uint8_t *>(rec.data());
    uint32_t flag;
    float scalar_label;
    std::memcpy(&flag, p, 4);
    std::memcpy(&scalar_label, p + 4, 4);
    const uint8_t *img = p + 24;
    size_t img_len = rec.size() - 24;
    if (flag > 0) {  // label array of `flag` floats precedes the image
      size_t lbytes = static_cast<size_t>(flag) * 4;
      if (img_len < lbytes) return false;
      int n = std::min<int>(flag, cfg_.label_width);
      std::memcpy(out_label, img, static_cast<size_t>(n) * 4);
      img += lbytes;
      img_len -= lbytes;
    } else {
      out_label[0] = scalar_label;
    }

    int h = 0, w = 0;
    bool ok;
    if (img_len >= 4 && std::memcmp(img, "RAW0", 4) == 0) {
      ok = DecodeRaw0(img, img_len, decoded, &h, &w);
    } else {
      ok = DecodeJpeg(img, img_len, cfg_.resize_px, decoded, row_scratch, &h, &w);
    }
    if (!ok) return false;

    // resize shorter side to resize_px (keeping aspect), then crop H×W
    int rh = h, rw = w;
    if (cfg_.resize_px > 0) {
      if (h < w) {
        rh = cfg_.resize_px;
        rw = std::max(cfg_.out_w, w * cfg_.resize_px / std::max(1, h));
      } else {
        rw = cfg_.resize_px;
        rh = std::max(cfg_.out_h, h * cfg_.resize_px / std::max(1, w));
      }
    }
    rh = std::max(rh, cfg_.out_h);
    rw = std::max(rw, cfg_.out_w);
    const uint8_t *src = decoded->data();
    if (rh != h || rw != w) {
      resized->resize(static_cast<size_t>(rh) * rw * 3);
      ResizeBilinear(decoded->data(), h, w, resized->data(), rh, rw);
      src = resized->data();
    }
    int y0, x0;
    if (cfg_.rand_crop) {
      y0 = rh == cfg_.out_h ? 0 : static_cast<int>(rng() % (rh - cfg_.out_h + 1));
      x0 = rw == cfg_.out_w ? 0 : static_cast<int>(rng() % (rw - cfg_.out_w + 1));
    } else {
      y0 = (rh - cfg_.out_h) / 2;
      x0 = (rw - cfg_.out_w) / 2;
    }
    bool mirror = cfg_.rand_mirror && (rng() & 1);
    for (int y = 0; y < cfg_.out_h; ++y) {
      const uint8_t *row = src + (static_cast<size_t>(y0 + y) * rw + x0) * 3;
      uint8_t *dst = out_px + static_cast<size_t>(y) * cfg_.out_w * 3;
      if (!mirror) {
        std::memcpy(dst, row, static_cast<size_t>(cfg_.out_w) * 3);
      } else {
        for (int x = 0; x < cfg_.out_w; ++x) {
          const uint8_t *s = row + (cfg_.out_w - 1 - x) * 3;
          dst[3 * x] = s[0];
          dst[3 * x + 1] = s[1];
          dst[3 * x + 2] = s[2];
        }
      }
    }
    return true;
  }

  void *rec_;
  PipeConfig cfg_;
  std::vector<std::thread> workers_;
  std::mutex mu_, rec_mu_;
  std::condition_variable cv_push_, cv_pop_, cv_rec_;
  std::deque<std::unique_ptr<ImgBatch>> queue_;
  std::deque<std::string> pending_;
  std::atomic<int64_t> emitted_{0};
  std::unique_ptr<ImgBatch> tmpl_;  // clone source for pad batches (mu_)
  std::atomic<bool> stop_{false};
  bool stream_end_ = false;
  int workers_done_ = 0;
  int real_done_ = 0;  // workers that ran out of records this epoch (mu_)
  int epoch_ = 0;
  std::string error_;
};

}  // namespace

extern "C" {

int mxtpu_imgpipe_open(const char *path, int batch_size, int out_h, int out_w,
                       int resize_px, int num_threads, int queue_depth,
                       int shard_index, int num_shards, int rand_crop,
                       int rand_mirror, int shuffle, int label_width,
                       uint64_t seed, void **out_handle) {
  if (batch_size < 1 || out_h < 1 || out_w < 1 || resize_px < 0) {
    mxtpu::SetError("imgpipe: batch_size/out_h/out_w must be positive "
                    "(a worker-thread length_error would kill the process)");
    return 1;
  }
  if (num_shards < 1) num_shards = 1;
  // one skip-mode scan per open: yields the logical record count for the
  // per-shard batch target AND validates framing up front
  int64_t n_total = mxtpu_rec_count(path);
  if (n_total < 0) {
    mxtpu::SetError(std::string("corrupt or unreadable record file: ") +
                    path);
    return 1;
  }
  void *rec = nullptr;
  if (mxtpu_rec_open(path, std::max(64, batch_size), 4, shard_index,
                     num_shards, &rec)) {
    return 1;
  }
  PipeConfig cfg;
  cfg.batch_size = batch_size;
  cfg.out_h = out_h;
  cfg.out_w = out_w;
  cfg.resize_px = resize_px;
  cfg.num_threads = std::max(1, num_threads);
  cfg.queue_depth = std::max(1, queue_depth);
  cfg.rand_crop = rand_crop;
  cfg.rand_mirror = rand_mirror;
  cfg.shuffle = shuffle;
  cfg.label_width = std::max(1, label_width);
  cfg.seed = seed;
  int64_t max_shard = (n_total + num_shards - 1) / num_shards;
  cfg.target_batches = (max_shard + batch_size - 1) / batch_size;
  *out_handle = new ImagePipeline(rec, cfg);
  return 0;
}

void mxtpu_imgpipe_close(void *handle) {
  delete static_cast<ImagePipeline *>(handle);
}

int mxtpu_imgpipe_next(void *handle, void **out_batch) {
  ImgBatch *b = nullptr;
  int rc = static_cast<ImagePipeline *>(handle)->Next(&b);
  if (rc < 0) return 1;
  *out_batch = b;  // null at end of epoch
  return 0;
}

void mxtpu_imgpipe_get(void *batch, const uint8_t **data, const float **labels,
                       int *count) {
  auto *b = static_cast<ImgBatch *>(batch);
  *data = b->data.data();
  *labels = b->labels.data();
  *count = b->count;
}

void mxtpu_imgpipe_free(void *batch) { delete static_cast<ImgBatch *>(batch); }

int mxtpu_imgpipe_reset(void *handle) {
  return static_cast<ImagePipeline *>(handle)->Reset();
}

}  // extern "C"
