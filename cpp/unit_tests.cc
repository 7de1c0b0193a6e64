// Native unit tier: plain-assert tests of the C ABI, no framework.
//
// The reference's gtest tier (test/unittest/*.cc, one dmlc_unittest binary)
// covers its C++ library directly; this is the same tier for the native
// core — built and run by `make -C cpp test` and wired into pytest via
// tests/test_cpp_unit.py. The Python parity suite (tests/test_native.py)
// covers native-vs-Python agreement; this tier covers C++-only invariants
// (bounds, error codes, adversarial framing) without a Python interpreter
// in the loop.

#include <cassert>
#include <cinttypes>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <algorithm>
#include <cstring>
#include <string>
#include <vector>

// All ABI declarations come from the public header — definitions
// are compile-checked against it in every TU.
#include "dmlc_tpu.h"


namespace {

int g_checks = 0;

#define CHECK_TRUE(cond)                                                   \
  do {                                                                     \
    ++g_checks;                                                            \
    if (!(cond)) {                                                         \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond); \
      std::exit(1);                                                        \
    }                                                                      \
  } while (0)

bool near(double a, double b, double tol = 1e-6) {
  double d = a - b;
  if (d < 0) d = -d;
  double m = (a < 0 ? -a : a) + (b < 0 ? -b : b) + 1e-12;
  return d <= tol * m || d <= tol;
}

struct SvmOut {
  std::vector<float> labels, weights, values;
  std::vector<int64_t> qids, row_nnz;
  std::vector<uint64_t> indices;
  int64_t rows = 0, nnz = 0;
  int flags = 0;
  int rc = 0;
};

SvmOut run_libsvm(const std::string& text, int64_t cap = -1) {
  SvmOut o;
  int64_t bound = cap >= 0 ? cap : static_cast<int64_t>(text.size()) / 2 + 2;
  o.labels.resize(bound + 1);
  o.weights.resize(bound + 1);
  o.values.resize(bound + 1);
  o.qids.resize(bound + 1);
  o.row_nnz.resize(bound + 1);
  o.indices.resize(bound + 1);
  o.rc = parse_libsvm(text.data(), text.size(), o.labels.data(),
                      o.weights.data(), o.qids.data(), o.row_nnz.data(),
                      o.indices.data(), o.values.data(), bound, bound,
                      &o.rows, &o.nnz, &o.flags);
  return o;
}

void test_libsvm_basic() {
  SvmOut o = run_libsvm("1 1:0.5 7:2.25\n0:3.5 3:1e-3 4:-2.5e2\n");
  CHECK_TRUE(o.rc == 0);
  CHECK_TRUE(o.rows == 2 && o.nnz == 4);
  CHECK_TRUE(near(o.labels[0], 1.0) && near(o.labels[1], 0.0));
  CHECK_TRUE(near(o.weights[1], 3.5));  // label:weight form
  CHECK_TRUE(o.flags & 1);              // HAS_WEIGHT
  CHECK_TRUE(o.indices[1] == 7 && near(o.values[1], 2.25));
  CHECK_TRUE(near(o.values[2], 1e-3) && near(o.values[3], -250.0));
}

void test_libsvm_qid_and_bare() {
  SvmOut o = run_libsvm("2 qid:42 3 5\n");
  CHECK_TRUE(o.rc == 0 && o.rows == 1 && o.nnz == 2);
  CHECK_TRUE(o.qids[0] == 42 && (o.flags & 2));
  CHECK_TRUE(near(o.values[0], 1.0) && near(o.values[1], 1.0));  // bare idx
}

void test_libsvm_errors() {
  CHECK_TRUE(run_libsvm("not_a_number 1:2\n").rc == -2);  // EPARSE
  CHECK_TRUE(run_libsvm("1 1:0.5\n0 2:1.5\n", 1).rc == -1);  // EOVERFLOW
}

void test_libsvm_numeric_edges() {
  SvmOut o = run_libsvm(
      "1 1:0.000000000000000000123 2:1e-999999999 3:0." +
      std::string(420, '0') + "5e450 4:2e999999999\n");
  CHECK_TRUE(o.rc == 0 && o.nnz == 4);
  CHECK_TRUE(o.values[0] > 0.0f);                 // leading zeros kept
  CHECK_TRUE(o.values[1] == 0.0f);                // saturates to 0
  CHECK_TRUE(near(o.values[2], 5e29, 1e-3));      // compensating exponent
  CHECK_TRUE(o.values[3] > 1e30f && o.values[3] > 0);  // +inf
}

void test_libfm() {
  std::vector<float> labels(8), values(8);
  std::vector<uint64_t> fields(8), indices(8);
  std::vector<int64_t> row_nnz(8);
  int64_t rows, nnz;
  std::string text = "1 0:1:0.5 3:7:2.5\n0 1:2:-1.5\n";
  int rc = parse_libfm(text.data(), text.size(), labels.data(),
                       row_nnz.data(), fields.data(), indices.data(),
                       values.data(), 8, 8, &rows, &nnz);
  CHECK_TRUE(rc == 0 && rows == 2 && nnz == 3);
  CHECK_TRUE(fields[1] == 3 && indices[1] == 7 && near(values[1], 2.5));
  std::string bad = "1 0:1\n";  // missing third component
  rc = parse_libfm(bad.data(), bad.size(), labels.data(), row_nnz.data(),
                   fields.data(), indices.data(), values.data(), 8, 8,
                   &rows, &nnz);
  CHECK_TRUE(rc == -2);
}

void test_csv() {
  std::vector<float> out(16);
  int64_t rows, cols;
  std::string text = "1,0.5,2.5\n0,1.5,-3.5\n";
  CHECK_TRUE(parse_csv(text.data(), text.size(), out.data(), 4, 3, &rows,
                       &cols) == 0);
  CHECK_TRUE(rows == 2 && cols == 3 && near(out[5], -3.5));
  // inferred column count + empty cells parse as 0
  std::string text2 = "1,,2\n3,4,\n";
  CHECK_TRUE(parse_csv(text2.data(), text2.size(), out.data(), 4, 0, &rows,
                       &cols) == 0);
  CHECK_TRUE(cols == 3 && near(out[1], 0.0) && near(out[5], 0.0));
  // ragged row is a parse error
  std::string text3 = "1,2,3\n4,5\n";
  CHECK_TRUE(parse_csv(text3.data(), text3.size(), out.data(), 4, 0, &rows,
                       &cols) == -2);
}

void test_count_tokens() {
  int64_t rows, tokens;
  std::string text = "a bb  ccc\ndd\n\n";
  count_tokens(text.data(), text.size(), &rows, &tokens);
  CHECK_TRUE(tokens == 4);
  CHECK_TRUE(rows >= 3);  // upper bound contract: rows >= real row count
}

void test_recordio_roundtrip() {
  // payload containing the magic word mid-record (the adversarial case of
  // test/recordio_test.cc)
  const uint32_t kMagic = 0xced7230a;
  std::string payload = "hello";
  payload.append(reinterpret_cast<const char*>(&kMagic), 4);
  payload += "world";
  std::vector<char> packed(recordio_pack_bound(payload.data(),
                                               payload.size()));
  int64_t packed_len =
      recordio_pack(payload.data(), payload.size(), packed.data());
  CHECK_TRUE(packed_len > 0 && packed_len % 4 == 0);
  std::vector<char> out_data(payload.size() + 64);
  std::vector<int64_t> offsets(4);
  int64_t nrec, datalen, consumed;
  CHECK_TRUE(recordio_unpack(packed.data(), packed_len, out_data.data(),
                             offsets.data(), &nrec, &datalen,
                             &consumed) == 0);
  CHECK_TRUE(nrec == 1 && consumed == packed_len);
  CHECK_TRUE(datalen == static_cast<int64_t>(payload.size()));
  CHECK_TRUE(std::memcmp(out_data.data(), payload.data(), payload.size()) ==
             0);
  CHECK_TRUE(recordio_find_head(packed.data(), packed_len, 0) == 0);
}

void test_pipeline_end_to_end() {
  // two files, three parts: exactly-once row coverage through the full
  // native pipeline (reader thread + workers + ordered queue)
  char dir_template[] = "/tmp/dmlc_tpu_unit_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string paths_blob;
  std::vector<int64_t> sizes;
  std::vector<std::string> paths;
  int row_id = 0;
  for (int f = 0; f < 2; ++f) {
    std::string path = std::string(dir_template) + "/part" +
                       std::to_string(f) + ".svm";
    std::string content;
    for (int i = 0; i < 57; ++i, ++row_id) {
      content += std::to_string(row_id % 2) + " 1:" +
                 std::to_string(row_id) + ".25 2:0.5\n";
    }
    FILE* fp = std::fopen(path.c_str(), "wb");
    CHECK_TRUE(fp != nullptr);
    CHECK_TRUE(std::fwrite(content.data(), 1, content.size(), fp) ==
               content.size());
    std::fclose(fp);
    paths.push_back(path);
    sizes.push_back(static_cast<int64_t>(content.size()));
  }
  for (const std::string& p : paths) {
    paths_blob += p;
    paths_blob.push_back('\0');
  }
  int64_t total_rows = 0;
  for (int part = 0; part < 3; ++part) {
    void* h = ingest_open(paths_blob.data(), sizes.data(), 2, /*libsvm=*/0,
                          part, 3, /*nthread=*/2, /*chunk=*/1 << 16,
                          /*capacity=*/4, 0);
    CHECK_TRUE(h != nullptr);
    for (;;) {
      int64_t rows, nnz, ncols;
      int32_t flags;
      int rc = ingest_peek(h, &rows, &nnz, &ncols, &flags);
      CHECK_TRUE(rc >= 0);
      if (rc == 0) break;
      std::vector<float> labels(rows), values(nnz);
      std::vector<int64_t> offsets(rows + 1);
      std::vector<uint32_t> indices(nnz);
      CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                              offsets.data(), indices.data(), values.data(),
                              nullptr) == 1);
      CHECK_TRUE(offsets[rows] == nnz);
      total_rows += rows;
    }
    CHECK_TRUE(ingest_bytes_read(h) > 0);
    ingest_close(h);
  }
  CHECK_TRUE(total_rows == 114);  // every row in exactly one part
  for (const std::string& p : paths) std::remove(p.c_str());
  std::remove(dir_template);
}

void test_pipeline_early_close() {
  // tear the pipeline down while the reader and workers are mid-stream —
  // the cancellation path where lifetime races hide (run under TSan/ASan
  // by make test_tsan / test_asan)
  char dir_template[] = "/tmp/dmlc_tpu_unit_close_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string path = std::string(dir_template) + "/big.svm";
  std::string content;
  for (int i = 0; i < 20000; ++i) {
    content += std::to_string(i % 2) + " 1:0.125 2:0.5 3:0.75\n";
  }
  FILE* fp = std::fopen(path.c_str(), "wb");
  CHECK_TRUE(fp != nullptr);
  CHECK_TRUE(std::fwrite(content.data(), 1, content.size(), fp) ==
             content.size());
  std::fclose(fp);
  std::string blob = path;
  blob.push_back('\0');
  int64_t size = static_cast<int64_t>(content.size());
  for (int round = 0; round < 6; ++round) {
    void* h = ingest_open(blob.data(), &size, 1, 0, 0, 1, /*nthread=*/4,
                          /*chunk=*/1 << 14, /*capacity=*/2, 0);
    CHECK_TRUE(h != nullptr);
    // consume `round` blocks, then close with work still in flight
    for (int k = 0; k < round; ++k) {
      int64_t rows, nnz, ncols;
      int32_t flags;
      if (ingest_peek(h, &rows, &nnz, &ncols, &flags) != 1) break;
      std::vector<float> labels(rows), values(nnz);
      std::vector<int64_t> offsets(rows + 1);
      std::vector<uint32_t> indices(nnz);
      CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                              offsets.data(), indices.data(), values.data(),
                              nullptr) == 1);
    }
    ingest_close(h);
  }
  std::remove(path.c_str());
  std::remove(dir_template);
}

// Build one row-group payload (data/rowrec.py layout): labels f32[n],
// row_nnz u32[n] all = 1, indices u32[n] = 1, values f32[n].
std::string make_row_group(int base_label, int nrows, float value) {
  std::string p;
  p.push_back(0x52);  // tag
  p.push_back(4);     // flags: values
  p.push_back(0);
  p.push_back(0);
  uint32_t n = static_cast<uint32_t>(nrows);
  p.append(reinterpret_cast<const char*>(&n), 4);
  p.append(reinterpret_cast<const char*>(&n), 4);  // nnz == nrows
  for (int i = 0; i < nrows; ++i) {
    float lab = static_cast<float>((base_label + i) % 2);
    p.append(reinterpret_cast<const char*>(&lab), 4);
  }
  for (int i = 0; i < nrows; ++i) {
    uint32_t one = 1;
    p.append(reinterpret_cast<const char*>(&one), 4);
  }
  for (int i = 0; i < nrows; ++i) {
    uint32_t idx = 1;
    p.append(reinterpret_cast<const char*>(&idx), 4);
  }
  for (int i = 0; i < nrows; ++i) {
    p.append(reinterpret_cast<const char*>(&value), 4);
  }
  return p;
}

void test_pipeline_recordio_format() {
  // row-group records through the native pipeline at format=3, every
  // (part, nparts); values engineered to the magic bit pattern so payloads
  // carry aligned embedded magics (recordio_test.cc:17-47 adversarial)
  char dir_template[] = "/tmp/dmlc_tpu_unit_rio_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string path = std::string(dir_template) + "/rows.rec";
  float magic_value;
  uint32_t magic_bits = 0xced7230aU;
  std::memcpy(&magic_value, &magic_bits, 4);
  std::string framed;
  const int kGroups = 40, kRowsPer = 23;
  for (int g = 0; g < kGroups; ++g) {
    std::string payload = make_row_group(g * kRowsPer, kRowsPer, magic_value);
    std::string out(recordio_pack_bound(payload.data(), payload.size()), 0);
    int64_t wrote = recordio_pack(payload.data(), payload.size(), &out[0]);
    CHECK_TRUE(wrote > 0);
    framed.append(out.data(), wrote);
  }
  FILE* fp = std::fopen(path.c_str(), "wb");
  CHECK_TRUE(fp != nullptr);
  CHECK_TRUE(std::fwrite(framed.data(), 1, framed.size(), fp) ==
             framed.size());
  std::fclose(fp);
  std::string blob = path;
  blob.push_back('\0');
  int64_t size = static_cast<int64_t>(framed.size());
  for (int nparts : {1, 2, 3, 7}) {
    int64_t total_rows = 0;
    for (int part = 0; part < nparts; ++part) {
      void* h = ingest_open(blob.data(), &size, 1, /*recordio=*/3, part,
                            nparts, /*nthread=*/2, /*chunk=*/1 << 12,
                            /*capacity=*/4, 0);
      CHECK_TRUE(h != nullptr);
      for (;;) {
        int64_t rows, nnz, ncols;
        int32_t flags;
        int rc = ingest_peek(h, &rows, &nnz, &ncols, &flags);
        CHECK_TRUE(rc >= 0);
        if (rc == 0) break;
        CHECK_TRUE(nnz == rows);
        std::vector<float> labels(rows), values(nnz);
        std::vector<int64_t> offsets(rows + 1);
        std::vector<uint32_t> indices(nnz);
        CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                                offsets.data(), indices.data(), values.data(),
                                nullptr) == 1);
        for (int64_t i = 0; i < nnz; ++i) {
          uint32_t bits;
          std::memcpy(&bits, &values[i], 4);
          CHECK_TRUE(bits == magic_bits);
          CHECK_TRUE(indices[i] == 1);
        }
        total_rows += rows;
      }
      ingest_close(h);
    }
    CHECK_TRUE(total_rows == kGroups * kRowsPer);
  }
  std::remove(path.c_str());
  std::remove(dir_template);
}

void test_pipeline_batch_staging() {
  // fixed-shape batch fetch: dense fill + COO fill agree with the row
  // stream, partial blocks carry across batches, staging survives close
  // with rows still staged
  char dir_template[] = "/tmp/dmlc_tpu_unit_batch_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string path = std::string(dir_template) + "/b.svm";
  std::string content;
  const int kRows = 1003;  // not a multiple of the batch size
  for (int i = 0; i < kRows; ++i) {
    content += std::to_string(i % 2) + " 1:" + std::to_string(i) +
               ".5 3:0.25\n";
  }
  FILE* fp = std::fopen(path.c_str(), "wb");
  CHECK_TRUE(fp != nullptr);
  CHECK_TRUE(std::fwrite(content.data(), 1, content.size(), fp) ==
             content.size());
  std::fclose(fp);
  std::string blob = path;
  blob.push_back('\0');
  int64_t size = static_cast<int64_t>(content.size());

  // dense sweep
  void* h = ingest_open(blob.data(), &size, 1, 0, 0, 1, /*nthread=*/2,
                        /*chunk=*/1 << 14, /*capacity=*/4, 0);
  CHECK_TRUE(h != nullptr);
  const int64_t kBatch = 128, kFeat = 5;
  std::vector<float> x(kBatch * kFeat), labels(kBatch), weights(kBatch);
  int64_t seen = 0;
  for (;;) {
    int64_t rows, nnz;
    int rc = ingest_stage_batch(h, kBatch, &rows, &nnz);
    CHECK_TRUE(rc >= 0);
    if (rc == 0) break;
    CHECK_TRUE(nnz == rows * 2);
    int64_t got = ingest_fetch_batch_dense(h, x.data(), labels.data(),
                                           weights.data(), kBatch, kFeat);
    CHECK_TRUE(got == rows);
    for (int64_t i = 0; i < got; ++i) {
      int64_t row_id = seen + i;
      CHECK_TRUE(labels[i] == static_cast<float>(row_id % 2));
      CHECK_TRUE(weights[i] == 1.0f);
      CHECK_TRUE(x[i * kFeat + 1] == static_cast<float>(row_id) + 0.5f);
      CHECK_TRUE(x[i * kFeat + 3] == 0.25f);
      CHECK_TRUE(x[i * kFeat + 0] == 0.0f);
    }
    for (int64_t i = got; i < kBatch; ++i) CHECK_TRUE(weights[i] == 0.0f);
    seen += got;
  }
  CHECK_TRUE(seen == kRows);
  double stats[7] = {0};
  ingest_stats(h, stats, 7);
  CHECK_TRUE(stats[0] == static_cast<double>(content.size()));
  CHECK_TRUE(stats[4] > 0);  // parse_ns
  // the two CPU slots appended after the first seven; an old-length
  // buffer (above) is not written past its end
  double wide[10] = {0, 0, 0, 0, 0, 0, 0, 0, 0, -1.0};
  ingest_stats(h, wide, 10);
  CHECK_TRUE(wide[6] == stats[6]);
  CHECK_TRUE(wide[7] > 0);  // reader_cpu_ns
  CHECK_TRUE(wide[8] > 0);  // parse_cpu_ns
  CHECK_TRUE(wide[9] == -1.0);
  ingest_close(h);

  // COO sweep with an overflow probe, then close mid-stage
  h = ingest_open(blob.data(), &size, 1, 0, 0, 1, 2, 1 << 14, 4, 0);
  CHECK_TRUE(h != nullptr);
  int64_t rows, nnz;
  CHECK_TRUE(ingest_stage_batch(h, 100, &rows, &nnz) == 1);
  CHECK_TRUE(rows == 100 && nnz == 200);
  std::vector<int32_t> idx(256), row_ids(256), offs(101);
  std::vector<float> vals(256);
  // bucket too small: fails without consuming
  CHECK_TRUE(ingest_fetch_batch_coo(h, labels.data(), weights.data(),
                                    idx.data(), vals.data(), row_ids.data(),
                                    offs.data(), 100, 100) < 0);
  CHECK_TRUE(ingest_fetch_batch_coo(h, labels.data(), weights.data(),
                                    idx.data(), vals.data(), row_ids.data(),
                                    offs.data(), 100, 256) == 100);
  CHECK_TRUE(idx[0] == 1 && idx[1] == 3 && row_ids[2] == 1);
  // CSR offsets mirror row_ids: offsets[r] <= e < offsets[r+1] iff
  // row_ids[e] == r; final offset = valid nnz
  CHECK_TRUE(offs[0] == 0 && offs[100] == 200);
  for (int e = 0; e < 200; ++e) {
    int r = row_ids[e];
    CHECK_TRUE(offs[r] <= e && e < offs[r + 1]);
  }
  for (int k = 200; k < 256; ++k) CHECK_TRUE(vals[k] == 0.0f);
  CHECK_TRUE(ingest_stage_batch(h, 4096, &rows, &nnz) == 1);  // stage rest
  ingest_close(h);  // staged blocks must be freed (ASan tier checks)

  std::remove(path.c_str());
  std::remove(dir_template);
}

void test_batch_coo_sharded() {
  // entries partitioned by destination shard with local row ids; padding
  // no-ops; overflow consumes nothing
  char dir_template[] = "/tmp/dmlc_tpu_unit_shard_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string path = std::string(dir_template) + "/s.svm";
  std::string content;
  const int kRows = 64;
  for (int i = 0; i < kRows; ++i) {
    // row i has (i % 3) + 1 entries at features 1..
    std::string line = std::to_string(i % 2);
    for (int k = 0; k <= i % 3; ++k) {
      line += " " + std::to_string(k + 1) + ":" + std::to_string(i) + ".25";
    }
    content += line + "\n";
  }
  FILE* fp = std::fopen(path.c_str(), "wb");
  CHECK_TRUE(fp != nullptr);
  CHECK_TRUE(std::fwrite(content.data(), 1, content.size(), fp) ==
             content.size());
  std::fclose(fp);
  std::string blob = path;
  blob.push_back('\0');
  int64_t size = static_cast<int64_t>(content.size());
  void* h = ingest_open(blob.data(), &size, 1, 0, 0, 1, 2, 1 << 14, 4, 0);
  CHECK_TRUE(h != nullptr);
  int64_t rows, nnz;
  CHECK_TRUE(ingest_stage_batch(h, kRows, &rows, &nnz) == 1);
  CHECK_TRUE(rows == kRows);
  const int64_t kShards = 4, kRowsPer = kRows / kShards;
  int64_t max_shard = ingest_staged_max_shard_nnz(h, kRows, kShards);
  CHECK_TRUE(max_shard > 0 && max_shard < nnz);
  // undersized bucket: fails without consuming
  std::vector<float> labels(kRows), weights(kRows);
  {
    std::vector<int32_t> idx(kShards * (max_shard - 1));
    std::vector<float> vals(kShards * (max_shard - 1));
    std::vector<int32_t> rid(kShards * (max_shard - 1));
    std::vector<int32_t> off(kShards * (kRowsPer + 1));
    CHECK_TRUE(ingest_fetch_batch_coo_sharded(
                   h, labels.data(), weights.data(), idx.data(), vals.data(),
                   rid.data(), off.data(), kRows, kShards,
                   max_shard - 1) < 0);
  }
  int64_t bucket = max_shard;
  std::vector<int32_t> idx(kShards * bucket), rid(kShards * bucket);
  std::vector<int32_t> offs(kShards * (kRowsPer + 1));
  std::vector<float> vals(kShards * bucket);
  CHECK_TRUE(ingest_fetch_batch_coo_sharded(
                 h, labels.data(), weights.data(), idx.data(), vals.data(),
                 rid.data(), offs.data(), kRows, kShards, bucket) == kRows);
  // per-shard local offsets mirror the local row ids
  for (int64_t s = 0; s < kShards; ++s) {
    const int32_t* off = offs.data() + s * (kRowsPer + 1);
    CHECK_TRUE(off[0] == 0);
    for (int64_t e = 0; e < bucket; ++e) {
      if (vals[s * bucket + e] == 0.0f) continue;  // padding
      int32_t r = rid[s * bucket + e];
      CHECK_TRUE(off[r] <= e && e < off[r + 1]);
    }
  }
  // verify: every entry's value row matches its shard section + local id
  int64_t seen = 0;
  for (int64_t s = 0; s < kShards; ++s) {
    for (int64_t k = 0; k < bucket; ++k) {
      float v = vals[s * bucket + k];
      if (v == 0.0f) continue;  // padding
      int64_t global_row = s * kRowsPer + rid[s * bucket + k];
      CHECK_TRUE(v == static_cast<float>(global_row) + 0.25f);
      CHECK_TRUE(rid[s * bucket + k] >= 0 && rid[s * bucket + k] < kRowsPer);
      ++seen;
    }
  }
  CHECK_TRUE(seen == nnz);
  ingest_close(h);
  std::remove(path.c_str());
  std::remove(dir_template);
}

void test_push_reserve_commit() {
  // zero-copy push: write libsvm text into reserved tail space in odd-sized
  // slices, commit, and drain — row coverage must be exact
  void* h = ingest_open_push(/*libsvm=*/0, /*nthread=*/2, /*chunk=*/1 << 14,
                             /*capacity=*/4, 0);
  CHECK_TRUE(h != nullptr);
  const int kRows = 5000;
  std::string text;
  for (int i = 0; i < kRows; ++i) {
    text += std::to_string(i % 2) + " 1:" + std::to_string(i) + ".5\n";
  }
  int64_t off = 0;
  int64_t slice = 777;  // deliberately unaligned with chunk size
  while (off < static_cast<int64_t>(text.size())) {
    int64_t n = std::min<int64_t>(slice, text.size() - off);
    char* dst = static_cast<char*>(ingest_push_reserve(h, n));
    CHECK_TRUE(dst != nullptr);
    std::memcpy(dst, text.data() + off, n);
    CHECK_TRUE(ingest_push_commit(h, n) == 0);
    off += n;
    slice = slice * 3 % 4096 + 64;
  }
  CHECK_TRUE(ingest_push_eof(h) == 0);
  int64_t total = 0;
  for (;;) {
    int64_t rows, nnz, ncols;
    int32_t flags;
    int rc = ingest_peek(h, &rows, &nnz, &ncols, &flags);
    CHECK_TRUE(rc >= 0);
    if (rc == 0) break;
    std::vector<float> labels(rows), values(nnz);
    std::vector<int64_t> offsets(rows + 1);
    std::vector<uint32_t> indices(nnz);
    CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                            offsets.data(), indices.data(), values.data(),
                            nullptr) == 1);
    total += rows;
  }
  CHECK_TRUE(total == kRows);
  ingest_close(h);
}

// ingest_drive_push: the C-consumer remote-ingest driver. The "transport"
// here is a memory buffer served through the fetch callback in short,
// varying slices (what a ranged-GET loop looks like to the pipeline).
struct FetchCtx {
  const std::string* text;
  int64_t slice = 777;
  bool fail_at_half = false;
};

int64_t MemFetch(void* vctx, int64_t offset, char* buf, int64_t len) {
  FetchCtx* ctx = static_cast<FetchCtx*>(vctx);
  int64_t total = static_cast<int64_t>(ctx->text->size());
  if (ctx->fail_at_half && offset >= total / 2) return -1;  // transport err
  if (offset >= total) return 0;  // end of stream
  int64_t n = std::min<int64_t>(len, total - offset);
  n = std::min<int64_t>(n, ctx->slice);  // short reads
  ctx->slice = ctx->slice * 3 % 4096 + 64;
  std::memcpy(buf, ctx->text->data() + offset, static_cast<size_t>(n));
  return n;
}

void test_drive_push() {
  const int kRows = 5000;
  std::string text;
  for (int i = 0; i < kRows; ++i) {
    text += std::to_string(i % 2) + " 1:" + std::to_string(i) + ".5\n";
  }
  // unknown-length mode (total = -1): the callback's 0 return ends it
  void* h = ingest_open_push(/*libsvm=*/0, /*nthread=*/2, /*chunk=*/1 << 14,
                             /*capacity=*/4, 0);
  CHECK_TRUE(h != nullptr);
  FetchCtx ctx{&text};
  CHECK_TRUE(ingest_drive_push(h, MemFetch, &ctx, -1, 1 << 12) == 0);
  int64_t total_rows = 0;
  for (;;) {
    int64_t rows, nnz, ncols;
    int32_t flags;
    int rc = ingest_peek(h, &rows, &nnz, &ncols, &flags);
    CHECK_TRUE(rc >= 0);
    if (rc == 0) break;
    std::vector<float> labels(rows), values(nnz);
    std::vector<int64_t> offsets(rows + 1);
    std::vector<uint32_t> indices(nnz);
    CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                            offsets.data(), indices.data(), values.data(),
                            nullptr) == 1);
    total_rows += rows;
  }
  CHECK_TRUE(total_rows == kRows);
  ingest_close(h);

  // transport failure mid-stream must abort the pipeline: the driver
  // returns an error and consumers see a failure, not a clean EOF
  void* h2 = ingest_open_push(0, 1, 1 << 14, 4, 0);
  CHECK_TRUE(h2 != nullptr);
  FetchCtx bad{&text};
  bad.fail_at_half = true;
  CHECK_TRUE(ingest_drive_push(h2, MemFetch, &bad, -1, 1 << 12) < 0);
  int64_t rows, nnz, ncols;
  int32_t flags;
  CHECK_TRUE(ingest_peek(h2, &rows, &nnz, &ncols, &flags) < 0);
  ingest_close(h2);

  // premature EOF against a declared length (truncated object / short
  // body) must also fail, not deliver a clean-but-short stream
  void* h3 = ingest_open_push(0, 1, 1 << 14, 4, 0);
  CHECK_TRUE(h3 != nullptr);
  FetchCtx trunc{&text};
  CHECK_TRUE(ingest_drive_push(h3, MemFetch, &trunc,
                               static_cast<int64_t>(text.size()) * 2,
                               1 << 12) < 0);
  CHECK_TRUE(ingest_peek(h3, &rows, &nnz, &ncols, &flags) < 0);
  ingest_close(h3);
}

}  // namespace

// Deterministic structured fuzz of the chunk parsers (the adversarial
// counterpart of the strtonum fuzz harness, tools/strtonum.py): random
// bytes, bit-flipped valid records, token soup, and truncations. The value
// is in WHICH binary runs it — this same function executes under the
// ASan+UBSan and TSan tiers (make -C cpp test_asan/test_tsan), so every
// out-of-bounds read a malformed chunk could provoke is instrumented.
// Asserts only the parser CONTRACT: rc in {OK, EOVERFLOW, EPARSE} and
// in-bounds output counts; xorshift seed fixed for reproducibility.
void test_parser_fuzz() {
  uint64_t s = 0x9E3779B97F4A7C15ULL;
  auto next = [&s]() {
    s ^= s << 13;
    s ^= s >> 7;
    s ^= s << 17;
    return s;
  };
  const std::string base = "1 1:0.5 2:1.5\n0 3:2.5\n";
  const char* toks[] = {"1", ":", ".", "-", "e", "\n", " ", "qid:",
                        "99999999999999999999", "1e999999", "-.e-", "\r",
                        "0.00000000000000000000000000000001"};
  for (int it = 0; it < 3000; ++it) {
    std::string data;
    switch (it & 3) {
      case 0: {  // raw bytes
        int64_t n = static_cast<int64_t>(next() % 200);
        for (int64_t i = 0; i < n; ++i)
          data.push_back(static_cast<char>(next() & 0xFF));
        break;
      }
      case 1: {  // bit-flipped valid records
        data = base;
        for (int k = 0; k < 1 + static_cast<int>(next() % 7); ++k)
          data[next() % data.size()] = static_cast<char>(next() & 0xFF);
        break;
      }
      case 2: {  // token soup
        int n = 1 + static_cast<int>(next() % 50);
        for (int k = 0; k < n; ++k)
          data += toks[next() % (sizeof(toks) / sizeof(toks[0]))];
        break;
      }
      default:  // truncation
        data = base.substr(0, next() % (base.size() + 1));
    }
    int64_t bound = static_cast<int64_t>(data.size()) / 2 + 2;
    std::vector<float> labels(bound), weights(bound), values(bound);
    std::vector<int64_t> qids(bound), row_nnz(bound);
    std::vector<uint32_t> indices(bound), fields(bound);
    int64_t rows = -1, nnz = -1;
    int flags = 0;
    int rc = parse_libsvm32(data.data(), data.size(), labels.data(),
                            weights.data(), qids.data(), row_nnz.data(),
                            indices.data(), values.data(), bound, bound,
                            &rows, &nnz, &flags);
    CHECK_TRUE(rc == 0 || rc == -1 || rc == -2);
    if (rc == 0) CHECK_TRUE(rows >= 0 && rows <= bound && nnz >= 0 &&
                            nnz <= bound);
    rc = parse_libfm32(data.data(), data.size(), labels.data(),
                       row_nnz.data(), fields.data(), indices.data(),
                       values.data(), bound, bound, &rows, &nnz);
    CHECK_TRUE(rc == 0 || rc == -1 || rc == -2);
    if (rc == 0) CHECK_TRUE(rows >= 0 && rows <= bound && nnz >= 0 &&
                            nnz <= bound);
    // csv capacity contract: caller sizes out from the first line's comma
    // count (pipeline.cc ParseCsvChunk does the same before calling)
    int64_t commas = 0;
    for (char c : data) {
      if (c == '\n' || c == '\r') break;
      commas += (c == ',');
    }
    int64_t csv_rows = static_cast<int64_t>(data.size()) + 1;
    std::vector<float> csv_out(csv_rows * (commas + 2));
    int64_t cols = 0;
    rc = parse_csv(data.data(), data.size(), csv_out.data(),
                   csv_rows, 0, &rows, &cols);
    CHECK_TRUE(rc == 0 || rc == -1 || rc == -2);
    if (rc == 0) CHECK_TRUE(rows >= 0 && rows <= csv_rows && cols >= 0 &&
                            rows * cols <= static_cast<int64_t>(
                                csv_out.size()));
  }
}

void test_pipeline_shuffle_chunks() {
  // ingest_open_ex with a seed: chunk visit order is a seeded
  // permutation — deterministic per seed, exactly-once, and refused for
  // multi-file inputs (the streaming reader cannot reorder). Runs under
  // ASan/TSan via the sanitizer targets.
  char dir_template[] = "/tmp/dmlc_tpu_unit_shuf_XXXXXX";
  CHECK_TRUE(mkdtemp(dir_template) != nullptr);
  std::string path = std::string(dir_template) + "/s.svm";
  std::string content;
  for (int i = 0; i < 40000; ++i) {
    content += std::to_string(i % 2) + " 1:" + std::to_string(i) + ".0\n";
  }
  FILE* fp = std::fopen(path.c_str(), "wb");
  CHECK_TRUE(fp != nullptr);
  CHECK_TRUE(std::fwrite(content.data(), 1, content.size(), fp) ==
             content.size());
  std::fclose(fp);
  std::string blob = path;
  blob.push_back('\0');
  int64_t size = static_cast<int64_t>(content.size());

  auto run = [&](int64_t seed) {
    std::vector<float> order;
    void* h = ingest_open_ex(blob.data(), &size, 1, /*libsvm=*/0, 0, 1,
                             /*nthread=*/2, /*chunk=*/1 << 14,
                             /*capacity=*/4, 0, seed);
    CHECK_TRUE(h != nullptr);
    for (;;) {
      int64_t rows, nnz, ncols;
      int32_t flags;
      int rc = ingest_peek(h, &rows, &nnz, &ncols, &flags);
      CHECK_TRUE(rc >= 0);
      if (rc == 0) break;
      std::vector<float> labels(rows), values(nnz);
      std::vector<int64_t> offsets(rows + 1);
      std::vector<uint32_t> indices(nnz);
      CHECK_TRUE(ingest_fetch(h, labels.data(), nullptr, nullptr,
                              offsets.data(), indices.data(), values.data(),
                              nullptr) == 1);
      order.insert(order.end(), values.begin(), values.end());
    }
    ingest_close(h);
    return order;
  };

  std::vector<float> seq = run(-1);
  CHECK_TRUE(static_cast<int>(seq.size()) == 40000);
  for (int i = 0; i < 40000; ++i) CHECK_TRUE(seq[i] == (float)i);
  std::vector<float> s7 = run(7);
  std::vector<float> s7b = run(7);
  std::vector<float> s9 = run(9);
  CHECK_TRUE(s7 == s7b);   // deterministic per seed
  CHECK_TRUE(s7 != seq);   // actually shuffled
  CHECK_TRUE(s7 != s9);    // seed-sensitive
  std::vector<float> sorted7 = s7;
  std::sort(sorted7.begin(), sorted7.end());
  CHECK_TRUE(sorted7 == seq);  // exactly-once
  // multi-file shuffle request must be refused (NULL), not degraded
  std::string blob2 = blob;
  blob2 += path;
  blob2.push_back('\0');
  int64_t sizes2[2] = {size, size};
  CHECK_TRUE(ingest_open_ex(blob2.data(), sizes2, 2, 0, 0, 1, 2, 1 << 14,
                            4, 0, /*seed=*/3) == nullptr);
  std::remove(path.c_str());
  std::remove(dir_template);
}

int main() {
  CHECK_TRUE(dmlc_tpu_abi_version() >= 1);
  test_parser_fuzz();
  test_libsvm_basic();
  test_libsvm_qid_and_bare();
  test_libsvm_errors();
  test_libsvm_numeric_edges();
  test_libfm();
  test_csv();
  test_count_tokens();
  test_recordio_roundtrip();
  test_pipeline_end_to_end();
  test_pipeline_early_close();
  test_pipeline_batch_staging();
  test_pipeline_recordio_format();
  test_batch_coo_sharded();
  test_push_reserve_commit();
  test_drive_push();
  test_pipeline_shuffle_chunks();
  std::printf("cpp unit tests ok (%d checks)\n", g_checks);
  return 0;
}
