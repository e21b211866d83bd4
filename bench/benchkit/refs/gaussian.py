"""Plain reference of the gaussian3x3 configuration, independent of the
program under test.

A 3x3 Gaussian filter (kernel [[1,2,1],[2,4,2],[1,2,1]] / 16) built from
nine 8-bit unsigned multipliers and eight 16-bit adders, one genome gene
per slot, each gene an index into the circuit catalogue below.  The
circuit models are copies of the behavioural models the configuration
names (EvoApprox8b-style multipliers, FPGA approximate adders), written
per genome with no tables, batching or device code.  The catalogue order
is part of the configuration: a genome means the same circuits here and
in the program.

``precision="bfloat16"`` is the control: the same filter with every
product and partial sum rounded to bfloat16, the step a later change
could take by moving the filter onto the matrix unit.
"""

from __future__ import annotations

import functools

import numpy as np

COEFFS = np.array([1, 2, 1, 2, 4, 2, 1, 2, 1], dtype=np.int64)
# adder-tree wiring: operand indices; products are 0..8, adder outputs 9..16
TREE = [(0, 1), (2, 3), (4, 5), (6, 7), (9, 10), (11, 12), (13, 14), (15, 8)]
PSNR_CAP = 100.0


# --------------------------------------------------------------------------
# 8-bit unsigned multipliers
# --------------------------------------------------------------------------

def _u8(x):
    return np.asarray(x, dtype=np.int64) & 0xFF


def mul_exact(a, b):
    return _u8(a) * _u8(b)


def mul_trunc(a, b, *, k):
    a, b = _u8(a), _u8(b)
    return ((a >> k) * (b >> k)) << (2 * k)


def mul_perforated(a, b, *, k):
    a, b = _u8(a), _u8(b)
    p = np.zeros_like(a)
    for i in range(k, 8):
        p = p + ((a >> i) & 1) * (b << i)
    return p


def mul_broken_array(a, b, *, k):
    a, b = _u8(a), _u8(b)
    mask = ~np.int64((1 << k) - 1)
    p = np.zeros_like(a)
    for i in range(8):
        p = p + (((a >> i) & 1) * (b << i) & mask)
    return p


def _ilog2(x):
    return np.frexp(x.astype(np.float64))[1].astype(np.int64) - 1


def mul_mitchell(a, b):
    a, b = _u8(a), _u8(b)
    nz = (a > 0) & (b > 0)
    a1, b1 = np.where(nz, a, 1), np.where(nz, b, 1)
    ka, kb = _ilog2(a1), _ilog2(b1)
    xa, xb = a1 - (np.int64(1) << ka), b1 - (np.int64(1) << kb)
    cross = xa * (np.int64(1) << kb) + xb * (np.int64(1) << ka)
    base = np.int64(1) << (ka + kb)
    return np.where(nz, np.where(cross < base, base + cross, 2 * cross), 0)


def mul_drum(a, b, *, k):
    a, b = _u8(a), _u8(b)
    nz = (a > 0) & (b > 0)
    a1, b1 = np.where(nz, a, 1), np.where(nz, b, 1)
    sa = np.maximum(_ilog2(a1) - (k - 1), 0)
    sb = np.maximum(_ilog2(b1) - (k - 1), 0)
    p = (((a1 >> sa) | 1) * ((b1 >> sb) | 1)) << (sa + sb)
    return np.where(nz, p, 0)


_K2 = np.array([[0, 0, 0, 0], [0, 1, 2, 3], [0, 2, 4, 6], [0, 3, 6, 7]],
               dtype=np.int64)


def _kulkarni(a, b, bits):
    if bits == 2:
        return _K2[a, b]
    h = bits // 2
    m = (1 << h) - 1
    return (_kulkarni(a & m, b & m, h)
            + ((_kulkarni(a & m, b >> h, h) + _kulkarni(a >> h, b & m, h)) << h)
            + (_kulkarni(a >> h, b >> h, h) << (2 * h)))


def mul_kulkarni(a, b):
    return _kulkarni(_u8(a), _u8(b), 8)


MUL8U = ([mul_exact]
         + [functools.partial(mul_trunc, k=k) for k in range(1, 7)]
         + [functools.partial(mul_perforated, k=k) for k in range(1, 7)]
         + [functools.partial(mul_broken_array, k=k) for k in range(2, 9, 2)]
         + [mul_mitchell]
         + [functools.partial(mul_drum, k=k) for k in range(3, 7)]
         + [mul_kulkarni])


# --------------------------------------------------------------------------
# 16-bit adders
# --------------------------------------------------------------------------

def _u16(x):
    return np.asarray(x, dtype=np.int64) & 0xFFFF


def add_exact(a, b):
    return _u16(a) + _u16(b)


def add_loa(a, b, *, k):
    a, b = _u16(a), _u16(b)
    m = (1 << k) - 1
    return (((a >> k) + (b >> k)) << k) + ((a | b) & m)


def add_trunc(a, b, *, k):
    a, b = _u16(a), _u16(b)
    mask = ~np.int64((1 << k) - 1)
    return (a & mask) + (b & mask)


def add_segmented(a, b, *, seg):
    a, b = _u16(a), _u16(b)
    out = np.zeros_like(a)
    nseg = (16 + seg - 1) // seg
    for i in range(nseg):
        lo = i * seg
        m = (1 << min(seg, 16 - lo)) - 1
        s = ((a >> lo) & m) + ((b >> lo) & m)
        if i < nseg - 1:
            s = s & m
        out = out + (s << lo)
    return out


def add_eta1(a, b, *, k):
    a, b = _u16(a), _u16(b)
    low = np.zeros_like(a)
    flood = np.zeros(a.shape, dtype=bool)
    for i in range(k - 1, -1, -1):
        ai, bi = (a >> i) & 1, (b >> i) & 1
        low = low | (np.where(flood, 1, ai | bi) << i)
        flood = flood | (ai & bi).astype(bool)
    return (((a >> k) + (b >> k)) << k) + low


def add_speculative(a, b, *, la):
    a, b = _u16(a), _u16(b)
    out = np.zeros_like(a)
    for i in range(17):
        lo = max(0, i - la)
        if i > lo:
            m = (1 << (i - lo)) - 1
            carry = ((((a >> lo) & m) + ((b >> lo) & m)) >> (i - lo)) & 1
        else:
            carry = np.zeros_like(a)
        out = out | (((((a >> i) & 1) + ((b >> i) & 1) + carry) & 1) << i)
    return out


ADD16 = ([add_exact]
         + [functools.partial(add_loa, k=k) for k in range(2, 9, 2)]
         + [functools.partial(add_trunc, k=k) for k in range(2, 9, 2)]
         + [functools.partial(add_segmented, seg=s) for s in (4, 8)]
         + [functools.partial(add_eta1, k=k) for k in (4, 8)]
         + [functools.partial(add_speculative, la=la) for la in (4, 8)])


# --------------------------------------------------------------------------
# the filter, its inputs and its quality metric
# --------------------------------------------------------------------------

def _bf16(x):
    """Round float64 values to the nearest bfloat16 (ties to even)."""
    f = np.asarray(x, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def gaussian_filter(image, genome, *, precision="int"):
    """One (H, W) image through the filter that ``genome`` selects ->
    (H-2, W-2) int64 outputs."""
    image = np.asarray(image, dtype=np.int64)
    genome = [int(g) for g in genome]
    h, w = image.shape
    vals = []
    for i in range(9):
        dy, dx = divmod(i, 3)
        win = image[dy:h - 2 + dy, dx:w - 2 + dx]
        vals.append(MUL8U[genome[i]](win, COEFFS[i]))
    for j, (ia, ib) in enumerate(TREE):
        vals.append(ADD16[genome[9 + j]](vals[ia], vals[ib]))
    if precision == "int":
        return vals[-1] >> 4
    if precision != "bfloat16":
        raise ValueError(f"unknown precision {precision!r}")
    acc = _bf16(vals[0])
    for v in vals[1:9]:
        acc = _bf16(acc + _bf16(v))
    return np.floor(_bf16(acc / 16.0)).astype(np.int64)


def exact_genome():
    return [0] * 17


def psnr(ref, out):
    """PSNR in dB with the peak taken from the exact output, capped."""
    ref = np.asarray(ref, dtype=np.float64)
    out = np.asarray(out, dtype=np.float64)
    peak = float(np.max(np.abs(ref))) or 1.0
    mse = float(np.mean((ref - out) ** 2))
    if mse == 0.0:
        return PSNR_CAP
    return float(min(10.0 * np.log10(peak * peak / mse), PSNR_CAP))


def qor(images, genome, *, precision="int"):
    """QoR of one genome over a stack of images: PSNR of the whole stack
    against the exact filter, as a campaign labels it."""
    ref = np.stack([gaussian_filter(x, exact_genome()) for x in images])
    out = np.stack([gaussian_filter(x, genome, precision=precision)
                    for x in images])
    return psnr(ref, out)


def sample_images(n, size, seed):
    """(n, size, size) int64 images with 8-bit values: gradients,
    sinusoids, blobs and texture noise, from ``seed``: the
    configuration's own image generator."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float64)
    yy, xx = yy / size, xx / size
    out = np.empty((n, size, size), dtype=np.int64)
    for i in range(n):
        fx, fy = rng.uniform(1, 6, size=2)
        phase = rng.uniform(0, 2 * np.pi, size=2)
        img = (0.35 * (xx * rng.uniform(-1, 1) + yy * rng.uniform(-1, 1) + 1.0)
               + 0.3 * (np.sin(2 * np.pi * fx * xx + phase[0]) * 0.5 + 0.5)
               + 0.2 * (np.sin(2 * np.pi * fy * yy + phase[1]) * 0.5 + 0.5))
        for _ in range(3):
            cx, cy = rng.uniform(0.2, 0.8, size=2)
            r = rng.uniform(0.05, 0.2)
            img += 0.3 * np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * r * r))
        img += 0.05 * rng.standard_normal((size, size))
        img = (img - img.min()) / (img.max() - img.min() + 1e-9)
        out[i] = np.clip(np.round(img * 255), 0, 255).astype(np.int64)
    return out
