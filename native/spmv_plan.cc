// Native SpMV plan builder (ops/spmv.py host-side layout).
//
// The blocked one-hot layout needs edges grouped by destination block with
// stable intra-block order — a counting-sort scatter, not a global argsort.
// numpy pays O(m log m) argsort + four fancy-indexed scatters (~3.4 s at
// 10M edges); this is two O(m) passes (~0.1 s).
//
// Pass 1 (matrel_spmv_counts): per-block edge counts — Python derives the
// capacity/refusal decisions from these (policy stays in Python, testable).
// Pass 2 (matrel_spmv_fill): scatter edges into the padded (nb, cap)
// tables in input order; edges past a block's capacity go to the overflow
// COO, stably sorted by row (segment_sum wants sorted ids).
//
// Pass 2' (matrel_spmv_fill_ragged): the same scatter into blocks of
// their own sizes (the chunks layout), no overflow, and then every block's
// slots put in row order (below).
//
// With hub chunks (PR 36: matrel_spmv_counts_hubs, matrel_spmv_fill_ragged_hubs)
// the same two passes send an edge whose source has a hub rank to a second
// set of ragged tables (rank, off, val) and every other edge to the main
// ones, in one walk over the edge list: no partitioned copy of it.
//
// Slot order within a block. matrel_spmv_fill (the blocks layout) keeps
// input order where the numpy path sorts by row — the matvec's one-hot
// contraction is order-agnostic, so its contract (tests assert it) is equal
// spmv RESULTS, not byte-equal layouts. Both chunks fills lay a block's main
// slots by destination row, stable inside a row, as the numpy path does
// (matrel_spmv_fill_ragged since PR 38, the main tables beside hub chunks
// since PR 51): EQUAL LAYOUTS, slot for slot (tests assert that too). Two
// kernels read the order (ops/pallas_spmv.py): the k-wide scatter — a chunk
// of 2,048 slots in row order names few rows, and its one-hot is as tall as
// they need and not the block's 512 — and the (max | min) reduction, whose
// segmented scan wants the slots of one destination row side by side in
// every row of 128. The hub tables of matrel_spmv_fill_ragged_hubs lie by
// the hub table's row (rank / 128) into vector registers of 1,024 slots
// (PR 42: a register names a short run of table rows and the hub kernel
// walks those alone), and INSIDE each register by destination row, stable
// (PR 51: the walk is indifferent to the order inside a register, the
// reduction's scan is not), as the numpy path lays them: equal layouts
// again. Sentinels: src = n_cols (hub rank = n_hubs), val = 0, and in the
// chunks fills off = the off of the block's last real slot (0 in a block
// with none; the blocks layout: 0), so that `off` never falls along a row
// of 128 whatever it holds — a padded slot of off 0 behind real slots of
// rows 0 and 5 would read to the scan as row 0's run going on.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <numeric>
#include <vector>

namespace {

// The slots [p0, p0 + n) of up to four parallel tables (a, b 32-bit, c 8-bit
// or null, d float) put by a key below `keys`, stable: per-key counts, their
// prefix sum, one scatter walk from a scratch copy of the run.
struct RunSort {
    std::vector<int64_t> at;
    std::vector<int32_t> t_a, t_b;
    std::vector<int8_t> t_c;
    std::vector<float> t_d;

    // key_of_b: the key is table b's value (a destination row); else table
    // a's value >> 7 (a hub table row)
    void run(int64_t p0, int64_t n, int64_t keys, bool key_of_b,
             int32_t* a, int32_t* b, int8_t* c, float* d) {
        if (n < 2) return;
        at.assign(keys + 1, 0);
        t_a.assign(a + p0, a + p0 + n);
        t_b.assign(b + p0, b + p0 + n);
        if (c) t_c.assign(c + p0, c + p0 + n);
        t_d.assign(d + p0, d + p0 + n);
        const int32_t* k = key_of_b ? t_b.data() : t_a.data();
        const int shift = key_of_b ? 0 : 7;
        for (int64_t i = 0; i < n; ++i) at[(k[i] >> shift) + 1]++;
        for (int64_t r = 0; r < keys; ++r) at[r + 1] += at[r];
        for (int64_t i = 0; i < n; ++i) {
            const int64_t p = p0 + at[k[i] >> shift]++;
            a[p] = t_a[i];
            b[p] = t_b[i];
            if (c) c[p] = t_c[i];
            d[p] = t_d[i];
        }
    }
};

// The padded slots [p0 + n, p1) of a block name the row of its last real
// slot (see the header: `off` never falls along a row of 128).
void pad_offs(int32_t* off, int64_t p0, int64_t n, int64_t p1) {
    if (n > 0) std::fill(off + p0 + n, off + p1, off[p0 + n - 1]);
}

}  // namespace

extern "C" {

int matrel_spmv_counts(const int64_t* rows, int64_t m, int64_t block,
                       int64_t nb, int64_t* counts) {
    if (block <= 0 || nb <= 0) return -1;
    std::memset(counts, 0, sizeof(int64_t) * nb);
    for (int64_t e = 0; e < m; ++e) {
        // test rows[e] itself: truncating division maps (-block, 0) to 0,
        // which would sneak negatives past a `b < 0` guard
        if (rows[e] < 0) return -1;
        int64_t b = rows[e] / block;
        if (b >= nb) return -1;
        counts[b]++;
    }
    return 0;
}

// Returns the overflow edge count written, or -1 on error. vals may be
// null (edge weight 1.0). Output tables are (nb, cap) row-major.
int64_t matrel_spmv_fill(const int64_t* rows, const int64_t* cols,
                         const float* vals, int64_t m, int64_t n_cols,
                         int64_t block, int64_t nb, int64_t cap,
                         int32_t width,
                         int32_t* src8, int8_t* lane, int32_t* off,
                         float* val,
                         int64_t* ov_rows, int64_t* ov_cols, float* ov_vals,
                         int64_t ov_cap) {
    if (block <= 0 || nb <= 0 || cap <= 0 || width <= 0) return -1;
    const int64_t slots = nb * cap;
    const int32_t sentinel8 = static_cast<int32_t>(n_cols / width);
    const int8_t sentinel_lane = static_cast<int8_t>(n_cols % width);
    for (int64_t s = 0; s < slots; ++s) {
        src8[s] = sentinel8;
        lane[s] = sentinel_lane;
    }
    std::memset(off, 0, sizeof(int32_t) * slots);
    std::memset(val, 0, sizeof(float) * slots);

    std::vector<int64_t> next(nb, 0);
    std::vector<int64_t> ov_idx;
    for (int64_t e = 0; e < m; ++e) {
        const int64_t r = rows[e];
        if (r < 0 || cols[e] < 0) return -1;
        const int64_t b = r / block;
        if (b >= nb) return -1;
        const int64_t slot = next[b]++;
        if (slot >= cap) {
            ov_idx.push_back(e);
            continue;
        }
        const int64_t p = b * cap + slot;
        const int64_t c = cols[e];
        src8[p] = static_cast<int32_t>(c / width);
        lane[p] = static_cast<int8_t>(c % width);
        off[p] = static_cast<int32_t>(r % block);
        val[p] = vals ? vals[e] : 1.0f;
    }
    const int64_t n_ov = static_cast<int64_t>(ov_idx.size());
    if (n_ov > ov_cap) return -1;
    // stable sort by row (ties keep input order) — matches numpy's
    // stable argsort-by-row then slot>=cap selection
    std::stable_sort(ov_idx.begin(), ov_idx.end(),
                     [rows](int64_t a, int64_t b) {
                         return rows[a] < rows[b];
                     });
    for (int64_t i = 0; i < n_ov; ++i) {
        const int64_t e = ov_idx[i];
        ov_rows[i] = rows[e];
        ov_cols[i] = cols[e];
        ov_vals[i] = vals ? vals[e] : 1.0f;
    }
    return n_ov;
}

// The chunks layout's fill: block b owns the flat slots
// [first[b], first[b+1]) (its chunks, laid one after the other), sized
// by Python from the counts so that nothing overflows. Sentinels as the
// header says; a block's entries lie by destination row, stable inside a
// row. Two walks, both O(m): the scatter by block in input order (938
// write heads at the Netflix shape, where a head a row would be 480,189
// and miss the cache at every write), then a counting sort by `off`
// inside each block through a scratch copy of its slots (RunSort).
// Returns 0, or -1 on an index out of range or a block past its slots.
int matrel_spmv_fill_ragged(const int64_t* rows, const int64_t* cols,
                            const float* vals, int64_t m, int64_t n_cols,
                            int64_t block, int64_t nb,
                            const int64_t* first, int32_t width,
                            int32_t* src8, int8_t* lane, int32_t* off,
                            float* val) {
    if (block <= 0 || nb <= 0 || width <= 0) return -1;
    const int64_t slots = first[nb];
    const int32_t sentinel8 = static_cast<int32_t>(n_cols / width);
    const int8_t sentinel_lane = static_cast<int8_t>(n_cols % width);
    for (int64_t s = 0; s < slots; ++s) {
        src8[s] = sentinel8;
        lane[s] = sentinel_lane;
    }
    std::memset(off, 0, sizeof(int32_t) * slots);
    std::memset(val, 0, sizeof(float) * slots);

    std::vector<int64_t> next(first, first + nb);
    for (int64_t e = 0; e < m; ++e) {
        const int64_t r = rows[e];
        if (r < 0 || cols[e] < 0) return -1;
        const int64_t b = r / block;
        if (b >= nb) return -1;
        const int64_t p = next[b]++;
        if (p >= first[b + 1]) return -1;
        const int64_t c = cols[e];
        src8[p] = static_cast<int32_t>(c / width);
        lane[p] = static_cast<int8_t>(c % width);
        off[p] = static_cast<int32_t>(r % block);
        val[p] = vals ? vals[e] : 1.0f;
    }

    // a block's real slots, first[b] .. next[b], by row
    RunSort sort;
    for (int64_t b = 0; b < nb; ++b) {
        const int64_t p0 = first[b], n = next[b] - p0;
        sort.run(p0, n, block, true, src8, off, lane, val);
        pad_offs(off, p0, n, first[b + 1]);
    }
    return 0;
}

// Pass 1 of a plan with hub chunks: per-block counts of the edges whose
// source is a hub (hub_rank[col] >= 0; hub_rank has n_cols entries).
// Returns 0, or -1 on an index out of range.
int matrel_spmv_counts_hubs(const int64_t* rows, const int64_t* cols,
                            int64_t m, int64_t n_cols, int64_t block,
                            int64_t nb, const int32_t* hub_rank,
                            int64_t* counts) {
    if (block <= 0 || nb <= 0) return -1;
    std::memset(counts, 0, sizeof(int64_t) * nb);
    for (int64_t e = 0; e < m; ++e) {
        const int64_t r = rows[e], c = cols[e];
        if (r < 0 || c < 0 || c >= n_cols) return -1;
        const int64_t b = r / block;
        if (b >= nb) return -1;
        if (hub_rank[c] >= 0) counts[b]++;
    }
    return 0;
}

// Pass 2 of a plan with hub chunks, one walk over the edges: an edge whose
// source has a rank goes to the hub tables (block b owns their flat slots
// [hub_first[b], hub_first[b+1]); hub_idx = the rank, n_hubs in padded
// slots), every other to the main tables, sentinels as
// matrel_spmv_fill_ragged's, a block's slots in input order. Then, a block
// at a time, counting sorts through a scratch copy (RunSort), no sort of
// the edge list: the main slots by `off`, as matrel_spmv_fill_ragged's;
// the hub slots by table row (rank / 128), which shares them out among
// registers of 1,024, and each register's by `off` (512 keys for at most
// 1,024 slots), its set of slots — and so the walk hub_walks reckons for it
// — what the sort by table row made it. Returns 0, or -1 on an index or a
// rank out of range or a block past its slots in either set.
int matrel_spmv_fill_ragged_hubs(const int64_t* rows, const int64_t* cols,
                                 const float* vals, int64_t m,
                                 int64_t n_cols, int64_t block, int64_t nb,
                                 const int32_t* hub_rank, int32_t n_hubs,
                                 const int64_t* first,
                                 const int64_t* hub_first, int32_t width,
                                 int32_t* src8, int8_t* lane, int32_t* off,
                                 float* val, int32_t* hub_idx,
                                 int32_t* hub_off, float* hub_val) {
    if (block <= 0 || nb <= 0 || width <= 0) return -1;
    const int64_t slots = first[nb], hub_slots = hub_first[nb];
    const int32_t sentinel8 = static_cast<int32_t>(n_cols / width);
    const int8_t sentinel_lane = static_cast<int8_t>(n_cols % width);
    for (int64_t s = 0; s < slots; ++s) {
        src8[s] = sentinel8;
        lane[s] = sentinel_lane;
    }
    std::memset(off, 0, sizeof(int32_t) * slots);
    std::memset(val, 0, sizeof(float) * slots);
    for (int64_t s = 0; s < hub_slots; ++s) hub_idx[s] = n_hubs;
    std::memset(hub_off, 0, sizeof(int32_t) * hub_slots);
    std::memset(hub_val, 0, sizeof(float) * hub_slots);

    std::vector<int64_t> next(first, first + nb);
    std::vector<int64_t> hub_next(hub_first, hub_first + nb);
    for (int64_t e = 0; e < m; ++e) {
        const int64_t r = rows[e], c = cols[e];
        if (r < 0 || c < 0 || c >= n_cols) return -1;
        const int64_t b = r / block;
        if (b >= nb) return -1;
        const int32_t rank = hub_rank[c];
        if (rank >= n_hubs) return -1;      // the sort below counts by rank
        const float v = vals ? vals[e] : 1.0f;
        if (rank >= 0) {
            const int64_t p = hub_next[b]++;
            if (p >= hub_first[b + 1]) return -1;
            hub_idx[p] = rank;
            hub_off[p] = static_cast<int32_t>(r % block);
            hub_val[p] = v;
        } else {
            const int64_t p = next[b]++;
            if (p >= first[b + 1]) return -1;
            src8[p] = static_cast<int32_t>(c / width);
            lane[p] = static_cast<int8_t>(c % width);
            off[p] = static_cast<int32_t>(r % block);
            val[p] = v;
        }
    }

    // a block's main slots by row, as matrel_spmv_fill_ragged lays them;
    // its real hub slots, hub_first[b] .. hub_next[b], by table row, and
    // then every register of 1,024 of them by destination row
    const int64_t table_rows = (static_cast<int64_t>(n_hubs) + 127) / 128;
    const int64_t reg = 1024;
    RunSort sort;
    for (int64_t b = 0; b < nb; ++b) {
        const int64_t m0 = first[b], mn = next[b] - m0;
        sort.run(m0, mn, block, true, src8, off, lane, val);
        pad_offs(off, m0, mn, first[b + 1]);
        const int64_t p0 = hub_first[b], n = hub_next[b] - p0;
        sort.run(p0, n, table_rows, false, hub_idx, hub_off, nullptr,
                 hub_val);
        for (int64_t r0 = 0; r0 < n; r0 += reg)
            sort.run(p0 + r0, std::min(reg, n - r0), block, true, hub_idx,
                     hub_off, nullptr, hub_val);
        pad_offs(hub_off, p0, n, hub_first[b + 1]);
    }
    return 0;
}

}  // extern "C"
