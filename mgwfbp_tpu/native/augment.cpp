// Native data-path kernels of the host-side loader (mgwfbp_tpu/data): the
// transform a pool worker applies to a batch, as ONE pass over the uint8
// source that writes normalized float32 once. The reference leans on
// torchvision's C/libjpeg transforms inside torch DataLoader workers
// (SURVEY.md §2.8); these are the framework's own native equivalents:
//   fused_crop_flip_normalize  CIFAR: RandomCrop(pad) + flip + normalize
//   fused_rrc_flip_normalize   ImageNet: RandomResizedCrop + flip + normalize
//   normalize_u8               no augmentation, every `val` loader
// instead of NumPy's pad / gather -> flip -> cast -> normalize chains (each a
// full-batch memory round trip, the gathers under the GIL).
//
// Randomness stays in Python (offsets, rectangles and flips are drawn with
// the same seeded generator and in the same order as the NumPy fallback), and
// every float operation is written in the fallback's order, so both paths are
// bit-identical and the fallback is always available: no build step is
// required to train.
//
// Build (done lazily by native/__init__.py `_build`, on first use):
//   g++ -O3 -ffp-contract=off -shared -fPIC -std=c++17 -o <so> augment.cpp
// No -ffast-math and no FMA contraction: either would change the bits.

#include <cmath>
#include <cstdint>
#include <vector>

namespace {

// Per-channel affine of `normalize_images`: out = px * scale - shift.
struct Affine {
  float scale[16];
  float shift[16];
  Affine(int64_t c, const float* mean, const float* stddev) {
    for (int64_t k = 0; k < c && k < 16; ++k) {
      scale[k] = 1.0f / (255.0f * stddev[k]);
      shift[k] = mean[k] / stddev[k];
    }
  }
};

// One source row of a crop, blended horizontally into `dst` (w pixels, the
// output's column order): f[x0] * (1 - wx) + f[x1] * wx, as augment.py's
// `top_row` / `bot_row`. `col0` / `col1` are byte offsets into the row.
template <int C>
inline void blend_row(const uint8_t* src, float* dst, int64_t w, int64_t c,
                      const int64_t* col0, const int64_t* col1,
                      const int64_t* dcol, const float* wx,
                      const float* one_minus_wx) {
  const int64_t cc = C ? C : c;
  for (int64_t j = 0; j < w; ++j) {
    const uint8_t* a = src + col0[j];
    const uint8_t* b = src + col1[j];
    float* d = dst + dcol[j];
    const float wa = one_minus_wx[j];
    const float wb = wx[j];
    for (int64_t k = 0; k < cc; ++k)
      d[k] = (float)a[k] * wa + (float)b[k] * wb;
  }
}

}  // namespace

extern "C" {

// x: (B, H, W, C) uint8. out: (B, H, W, C) float32.
// oy/ox: (B,) crop offsets into the zero-padded image (0..2*pad).
// flip: (B,) 0/1 horizontal flip AFTER the crop.
// mean/std: (C,) normalization in 0..1 scale: out = (x/255 - mean) / std.
void fused_crop_flip_normalize(
    const uint8_t* x, float* out,
    int64_t b, int64_t h, int64_t w, int64_t c,
    int64_t pad,
    const int64_t* oy, const int64_t* ox, const uint8_t* flip,
    const float* mean, const float* stddev) {
  const Affine affine(c, mean, stddev);
  const float* scale = affine.scale;
  const float* shift = affine.shift;
  for (int64_t i = 0; i < b; ++i) {
    const uint8_t* img = x + i * h * w * c;
    float* dst = out + i * h * w * c;
    const int64_t top = oy[i] - pad;   // source row of output row 0
    const int64_t left = ox[i] - pad;  // source col of output col 0
    const bool fl = flip[i] != 0;
    for (int64_t y = 0; y < h; ++y) {
      const int64_t sy = y + top;
      float* row = dst + y * w * c;
      if (sy < 0 || sy >= h) {  // fully padded row -> normalized zeros
        for (int64_t xcol = 0; xcol < w; ++xcol)
          for (int64_t k = 0; k < c; ++k) row[xcol * c + k] = -shift[k];
        continue;
      }
      const uint8_t* srow = img + sy * w * c;
      for (int64_t xcol = 0; xcol < w; ++xcol) {
        // output col xcol reads crop col (flipped or not)
        const int64_t cc = fl ? (w - 1 - xcol) : xcol;
        const int64_t sx = cc + left;
        float* px = row + xcol * c;
        if (sx < 0 || sx >= w) {
          for (int64_t k = 0; k < c; ++k) px[k] = -shift[k];
        } else {
          const uint8_t* sp = srow + sx * c;
          for (int64_t k = 0; k < c; ++k)
            px[k] = (float)sp[k] * scale[k] - shift[k];
        }
      }
    }
  }
}

// RandomResizedCrop + horizontal flip + normalize of a (B, H, W, C) uint8
// batch into (B, H, W, C) float32: what data/augment.py's
// `random_resized_crop`, `random_hflip` and `normalize_images` compute in
// turn, operation for operation, so the bits are theirs.
// top/left/ch/cw: (B,) crop rectangles inside the image (1 <= ch <= h,
// 1 <= cw <= w; the caller checks). flip: (B,) 0/1, applied after the resize.
//
// A crop is never larger than the output, so successive output rows share
// source rows: each source row is blended horizontally once (into the flipped
// column order where the image flips) and kept while an output row needs it;
// an output row is then a contiguous blend of two such rows and the affine.
void fused_rrc_flip_normalize(
    const uint8_t* x, float* out,
    int64_t b, int64_t h, int64_t w, int64_t c,
    const int64_t* top, const int64_t* left,
    const int64_t* ch, const int64_t* cw, const uint8_t* flip,
    const float* mean, const float* stddev) {
  if (c < 1 || c > 16) return;  // the binding refuses these
  const Affine affine(c, mean, stddev);
  const int64_t wc = w * c;
  // the affine spread over a row, so the row loop has no channel index
  std::vector<float> scale(wc), shift(wc);
  for (int64_t j = 0; j < wc; ++j) {
    scale[j] = affine.scale[j % c];
    shift[j] = affine.shift[j % c];
  }
  std::vector<int64_t> col0(w), col1(w), dcol(w);
  std::vector<float> wx(w), one_minus_wx(w);
  std::vector<float> rows(2 * wc);
  for (int64_t i = 0; i < b; ++i) {
    const uint8_t* img = x + i * h * wc;
    float* dst = out + i * h * wc;
    const int64_t xlo = left[i], xhi = left[i] + cw[i] - 1;
    const int64_t ylo = top[i], yhi = top[i] + ch[i] - 1;
    const bool fl = flip[i] != 0;
    for (int64_t j = 0; j < w; ++j) {
      // half-pixel centres, in float64 as NumPy computes them
      const double xx =
          ((double)left[i] + ((double)j + 0.5) * (double)cw[i] / (double)w)
          - 0.5;
      const double x0f = std::floor(xx);
      int64_t x0 = (int64_t)x0f;
      x0 = x0 < xlo ? xlo : (x0 > xhi ? xhi : x0);
      const int64_t x1 = x0 + 1 > xhi ? xhi : x0 + 1;
      wx[j] = (float)(xx - x0f);
      one_minus_wx[j] = 1.0f - wx[j];
      col0[j] = x0 * c;
      col1[j] = x1 * c;
      dcol[j] = (fl ? w - 1 - j : j) * c;
    }
    // the two blended source rows held, by source row index
    int64_t held[2] = {-1, -1};
    float* buf[2] = {rows.data(), rows.data() + wc};
    auto hold = [&](int slot, int64_t row) {
      if (held[slot] == row) return;
      const uint8_t* src = img + row * wc;
      const int64_t *c0 = col0.data(), *c1 = col1.data(), *dc = dcol.data();
      const float *a = wx.data(), *na = one_minus_wx.data();
      if (c == 3) blend_row<3>(src, buf[slot], w, c, c0, c1, dc, a, na);
      else if (c == 1) blend_row<1>(src, buf[slot], w, c, c0, c1, dc, a, na);
      else blend_row<0>(src, buf[slot], w, c, c0, c1, dc, a, na);
      held[slot] = row;
    };
    for (int64_t r = 0; r < h; ++r) {
      const double yy =
          ((double)top[i] + ((double)r + 0.5) * (double)ch[i] / (double)h)
          - 0.5;
      const double y0f = std::floor(yy);
      int64_t y0 = (int64_t)y0f;
      y0 = y0 < ylo ? ylo : (y0 > yhi ? yhi : y0);
      const int64_t y1 = y0 + 1 > yhi ? yhi : y0 + 1;
      const float wy = (float)(yy - y0f);
      const float one_minus_wy = 1.0f - wy;
      // y0 never decreases with r, so it is usually the row that was y1:
      // keep it where it is held and refill the other slot
      const int s0 = held[1] == y0 ? 1 : 0;
      const int s1 = y1 == y0 ? s0 : 1 - s0;
      hold(s0, y0);
      hold(s1, y1);
      const float* t = buf[s0];
      const float* u = buf[s1];
      const float* sc = scale.data();
      const float* sh = shift.data();
      float* o = dst + r * wc;
      for (int64_t j = 0; j < wc; ++j)
        o[j] = (t[j] * one_minus_wy + u[j] * wy) * sc[j] - sh[j];
    }
  }
}

// Plain fused uint8 -> normalized float32 (eval path / no augmentation).
// n elements, channel minor-most. The affine is spread over a block of
// 16 * c elements (a whole number of pixels), so the loop divides nothing
// and the compiler vectorises it.
void normalize_u8(
    const uint8_t* x, float* out, int64_t n, int64_t c,
    const float* mean, const float* stddev) {
  if (c < 1 || c > 16) return;  // the binding refuses these; never loop on 0
  const Affine affine(c, mean, stddev);
  float scale[256];
  float shift[256];
  const int64_t block = 16 * c;
  for (int64_t j = 0; j < block; ++j) {
    scale[j] = affine.scale[j % c];
    shift[j] = affine.shift[j % c];
  }
  int64_t i = 0;
  for (; i + block <= n; i += block) {
    const uint8_t* p = x + i;
    float* o = out + i;
    for (int64_t j = 0; j < block; ++j)
      o[j] = (float)p[j] * scale[j] - shift[j];
  }
  for (int64_t j = 0; i + j < n; ++j)
    out[i + j] = (float)x[i + j] * scale[j] - shift[j];
}

}  // extern "C"
