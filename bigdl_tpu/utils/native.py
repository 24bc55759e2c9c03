"""Loader for the native host kernels (csrc/).

Reference: the lazy `.so`-from-jar loading of BigDL-core with
``MKL.isMKLLoaded`` guards at every call site (SURVEY.md section 2.1).
Same contract here: ``native_lib()`` returns the ctypes wrapper or None, and
every caller has a numpy fallback — the framework works on a host without a
C++ toolchain, just slower on the host preprocessing path. The binary is
never committed: it is built where it runs, with flags that do not depend on
the build machine's CPU (csrc/Makefile), and rebuilt whenever the source it
was built from differs from the one on disk.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess

import numpy as np

logger = logging.getLogger("bigdl_tpu.native")

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "libbigdl_tpu_native.so")
_STAMP = _SO + ".sha256"     # digest of the source the binary was built from

_lib = None
_tried = False


class _NativeLib:
    def __init__(self, dll):
        self._dll = dll
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        f32p = ctypes.POINTER(ctypes.c_float)
        dll.bigdl_crc32c.restype = ctypes.c_uint32
        dll.bigdl_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        dll.bigdl_fp16_compress.argtypes = [f32p, u16p, ctypes.c_uint64]
        dll.bigdl_fp16_decompress.argtypes = [u16p, f32p, ctypes.c_uint64]
        dll.bigdl_fp16_add.argtypes = [u16p, u16p, ctypes.c_uint64]
        dll.bigdl_resize_bilinear.argtypes = [u8p] + [ctypes.c_int] * 3 + \
            [u8p] + [ctypes.c_int] * 2
        dll.bigdl_hflip.argtypes = [u8p] + [ctypes.c_int] * 3
        dll.bigdl_normalize_chw.argtypes = [u8p] + [ctypes.c_int] * 3 + \
            [f32p, f32p, f32p]
        dll.bigdl_brightness_contrast.argtypes = [u8p, ctypes.c_uint64,
                                                  ctypes.c_float,
                                                  ctypes.c_float]
        dll.bigdl_saturation.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                         ctypes.c_float]
        dll.bigdl_crop.argtypes = [u8p] + [ctypes.c_int] * 7 + [u8p]
        u64p = ctypes.POINTER(ctypes.c_uint64)
        dll.bigdl_record_scan.restype = ctypes.c_int64
        dll.bigdl_record_scan.argtypes = [ctypes.c_char_p, u64p, u64p,
                                          ctypes.c_int64, ctypes.c_int]
        dll.bigdl_record_scan_mem.restype = ctypes.c_int64
        dll.bigdl_record_scan_mem.argtypes = [u8p, ctypes.c_uint64, u64p,
                                              u64p, ctypes.c_int64,
                                              ctypes.c_int]
        i32p = ctypes.POINTER(ctypes.c_int32)
        dll.bigdl_assemble_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, i32p, i32p, u8p, ctypes.c_int,
            ctypes.c_int, f32p, f32p, ctypes.c_int, f32p, ctypes.c_int]
        i64p = ctypes.POINTER(ctypes.c_int64)
        dll.bigdl_decode_sample.restype = ctypes.c_int64
        dll.bigdl_decode_sample.argtypes = [
            u8p, ctypes.c_uint64, i32p, i32p, i64p, u64p, u64p, i32p,
            ctypes.c_int32]

    @staticmethod
    def _u8(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))

    @staticmethod
    def _f32(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    @staticmethod
    def _u16(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16))

    def crc32c_bytes(self, data: bytes) -> int:
        return self._dll.bigdl_crc32c(data, len(data))

    def fp16_compress(self, arr):
        src = np.ascontiguousarray(arr, dtype=np.float32)
        out = np.empty(src.shape, dtype=np.uint16)
        self._dll.bigdl_fp16_compress(self._f32(src), self._u16(out), src.size)
        return out

    def fp16_decompress(self, arr):
        src = np.ascontiguousarray(arr, dtype=np.uint16)
        out = np.empty(src.shape, dtype=np.float32)
        self._dll.bigdl_fp16_decompress(self._u16(src), self._f32(out),
                                        src.size)
        return out

    def fp16_add(self, dst, src):
        assert dst.dtype == np.uint16 and src.dtype == np.uint16
        self._dll.bigdl_fp16_add(self._u16(dst), self._u16(src), dst.size)
        return dst

    def resize_bilinear(self, img, dh, dw):
        src = np.ascontiguousarray(img, dtype=np.uint8)
        h, w, c = src.shape
        out = np.empty((dh, dw, c), dtype=np.uint8)
        self._dll.bigdl_resize_bilinear(self._u8(src), h, w, c,
                                        self._u8(out), dh, dw)
        return out

    def hflip(self, img):
        img = np.ascontiguousarray(img, dtype=np.uint8)
        h, w, c = img.shape
        self._dll.bigdl_hflip(self._u8(img), h, w, c)
        return img

    def normalize_chw(self, img, mean, std):
        src = np.ascontiguousarray(img, dtype=np.uint8)
        h, w, c = src.shape
        mean = np.ascontiguousarray(mean, dtype=np.float32)
        std = np.ascontiguousarray(std, dtype=np.float32)
        out = np.empty((c, h, w), dtype=np.float32)
        self._dll.bigdl_normalize_chw(self._u8(src), h, w, c,
                                      self._f32(mean), self._f32(std),
                                      self._f32(out))
        return out

    def brightness_contrast(self, img, alpha=1.0, beta=0.0):
        img = np.ascontiguousarray(img, dtype=np.uint8)
        self._dll.bigdl_brightness_contrast(self._u8(img), img.size,
                                            alpha, beta)
        return img

    def saturation(self, img, alpha):
        img = np.ascontiguousarray(img, dtype=np.uint8)
        h, w, _ = img.shape
        self._dll.bigdl_saturation(self._u8(img), h, w, alpha)
        return img

    def record_scan(self, path, check_crc=True):
        """(offsets, lengths) of every framed record in a shard file
        (csrc bigdl_record_scan); raises IOError on corruption."""
        cap = max(1024, os.path.getsize(path) // 16 + 1)
        offsets = np.empty((cap,), dtype=np.uint64)
        lengths = np.empty((cap,), dtype=np.uint64)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        n = self._dll.bigdl_record_scan(
            path.encode(), offsets.ctypes.data_as(u64p),
            lengths.ctypes.data_as(u64p), cap, 1 if check_crc else 0)
        if n == -1:
            raise FileNotFoundError(path)
        if n < 0:
            raise IOError(f"{path}: corrupt record file (native scan {n})")
        return offsets[:n], lengths[:n]

    def record_scan_mem(self, data, check_crc=True, name="<buffer>"):
        """In-place (offsets, lengths) scan of a whole-shard buffer the
        caller already read — one file read total, no staging copies
        (csrc bigdl_record_scan_mem)."""
        buf = np.frombuffer(data, dtype=np.uint8)
        cap = max(1024, buf.size // 16 + 1)
        offsets = np.empty((cap,), dtype=np.uint64)
        lengths = np.empty((cap,), dtype=np.uint64)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        n = self._dll.bigdl_record_scan_mem(
            self._u8(buf), buf.size, offsets.ctypes.data_as(u64p),
            lengths.ctypes.data_as(u64p), cap, 1 if check_crc else 0)
        if n < 0:
            raise IOError(f"{name}: corrupt record buffer (native scan {n})")
        return offsets[:n], lengths[:n]

    def assemble_batch(self, imgs, y0s, x0s, flips, oh, ow, mean, std,
                       chw_out=True, out=None, n_threads=1):
        """Fused minibatch assembly (crop + hflip + normalize + layout)
        straight into the batch buffer; C++ threads split the records
        (reference ``MTLabeledBGRImgToBatch.scala:33``)."""
        n = len(imgs)
        h, w, c = imgs[0].shape
        for i, im in enumerate(imgs):
            if im.dtype != np.uint8:
                raise TypeError(
                    f"assemble_batch needs uint8 HWC images; image {i} is "
                    f"{im.dtype} — the C++ kernel would reinterpret its "
                    "bytes as pixels")
            if im.shape != (h, w, c):
                raise ValueError(
                    f"assemble_batch needs uniform image shapes; image {i} "
                    f"is {im.shape}, expected {(h, w, c)}")
        imgs = [np.ascontiguousarray(im) for im in imgs]
        ptrs = (ctypes.c_void_p * n)(
            *[im.ctypes.data_as(ctypes.c_void_p).value for im in imgs])
        y0s = np.ascontiguousarray(y0s, np.int32)
        x0s = np.ascontiguousarray(x0s, np.int32)
        flips = np.ascontiguousarray(flips, np.uint8)
        mean = np.ravel(np.ascontiguousarray(mean, np.float32))
        std = np.ravel(np.ascontiguousarray(std, np.float32))
        if mean.size < c or std.size < c:
            # the kernel reads c floats from each — shorter vectors would
            # be silent out-of-bounds reads
            raise ValueError(
                f"assemble_batch: mean/std have {mean.size}/{std.size} "
                f"entries for {c}-channel images")
        shape = (n, c, oh, ow) if chw_out else (n, oh, ow, c)
        if out is None:
            out = np.empty(shape, np.float32)
        elif (out.shape != shape or out.dtype != np.float32
                or not out.flags["C_CONTIGUOUS"]):
            raise ValueError(
                f"assemble_batch: out buffer must be C-contiguous float32 "
                f"{shape}, got {out.dtype} {out.shape}")
        i32p = ctypes.POINTER(ctypes.c_int32)
        self._dll.bigdl_assemble_batch(
            ptrs, n, h, w, c,
            y0s.ctypes.data_as(i32p), x0s.ctypes.data_as(i32p),
            self._u8(flips), oh, ow, self._f32(mean), self._f32(std),
            1 if chw_out else 0, self._f32(out), int(n_threads))
        return out

    # numpy dtype per C dtype-code table (csrc kDtypeNames; bfloat16 via
    # ml_dtypes, resolved lazily so the import stays optional)
    _DTYPE_CODES = ("float32", "float64", "int32", "int64", "uint8", "int8",
                    "uint16", "int16", "uint32", "uint64", "bool",
                    "float16", "bfloat16")
    _dtype_cache: dict = {}

    def _decode_scratch(self, max_tensors):
        """Reused per-thread metadata buffers + ctypes pointers for
        decode_sample_views — only ever hold parse METADATA consumed
        before return, never the tensor data itself."""
        import threading
        tl = self.__dict__.setdefault("_scratch_tl", threading.local())
        cache = getattr(tl, "bufs", None)
        if cache is None:
            cache = tl.bufs = {}
        if max_tensors not in cache:
            i32p = ctypes.POINTER(ctypes.c_int32)
            i64p = ctypes.POINTER(ctypes.c_int64)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            arrs = (np.empty(max_tensors, np.int32),
                    np.empty(max_tensors, np.int32),
                    np.empty(max_tensors * 8, np.int64),
                    np.empty(max_tensors, np.uint64),
                    np.empty(max_tensors, np.uint64),
                    np.zeros(3, np.int32))
            ptrs = (arrs[0].ctypes.data_as(i32p),
                    arrs[1].ctypes.data_as(i32p),
                    arrs[2].ctypes.data_as(i64p),
                    arrs[3].ctypes.data_as(u64p),
                    arrs[4].ctypes.data_as(u64p),
                    arrs[5].ctypes.data_as(i32p))
            cache[max_tensors] = (arrs, ptrs)
        return cache[max_tensors]

    def decode_sample_views(self, blob, max_tensors=16):
        """Parse one protowire Sample blob natively; returns
        (features, labels, feature_is_list, label_is_list) with each
        tensor a ZERO-COPY read-only numpy view over ``blob`` — no Python
        wire walk. Returns None when the record needs the slow path
        (exotic dtype, >max_tensors, malformed)."""
        buf = np.frombuffer(blob, dtype=np.uint8)
        (codes, ndims, shapes, offs, lens, meta), ptrs = \
            self._decode_scratch(max_tensors)
        n = self._dll.bigdl_decode_sample(
            self._u8(buf), buf.size, *ptrs, max_tensors)
        if n < 0:
            return None
        cache = self._dtype_cache
        tensors = []
        for i in range(n):
            code = int(codes[i])
            dt = cache.get(code)
            if dt is None:
                # one resolution rule for both decode paths
                from bigdl_tpu.dataset.record_file import _np_dtype
                dt = cache[code] = _np_dtype(self._DTYPE_CODES[code])
            shape = tuple(int(s) for s in
                          shapes[i * 8:i * 8 + int(ndims[i])])
            count = int(np.prod(shape)) if shape else 1
            if count * dt.itemsize != int(lens[i]):
                return None   # inconsistent record: slow path re-checks
            arr = np.frombuffer(blob, dtype=dt, count=count,
                                offset=int(offs[i])).reshape(shape)
            tensors.append(arr)
        nf = int(meta[0])
        return (tensors[:nf], tensors[nf:], bool(meta[1]), bool(meta[2]))

    def crop(self, img, y0, x0, ch, cw):
        src = np.ascontiguousarray(img, dtype=np.uint8)
        h, w, c = src.shape
        out = np.empty((ch, cw, c), dtype=np.uint8)
        self._dll.bigdl_crop(self._u8(src), h, w, c, y0, x0, ch, cw,
                             self._u8(out))
        return out


def _source_digest():
    """Content key of what the binary is built from. A copy of the tree
    scrambles mtimes, so staleness is decided by content, not by time."""
    h = hashlib.sha256()
    for name in ("bigdl_tpu_native.cpp", "Makefile"):
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build():
    """Build the library with ``make``. A host without a C++ toolchain is
    the documented optional case (False: callers use their numpy
    fallbacks); a toolchain that runs and fails is an error."""
    if shutil.which("make") is None or shutil.which("g++") is None:
        logger.warning("no C++ toolchain (make, g++): native host kernels "
                       "unavailable, using numpy fallbacks")
        return False
    r = subprocess.run(["make", "-B", "-C", _CSRC], capture_output=True,
                       text=True, timeout=300)
    if r.returncode:
        raise RuntimeError(f"building {_SO} failed:\n{r.stderr[-2000:]}")
    return True


def native_lib():
    """The ctypes wrapper, built on first use from csrc/ on this machine;
    None when there is no source or no toolchain to build it with."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(os.path.join(_CSRC, "bigdl_tpu_native.cpp")):
        return None
    digest = _source_digest()
    try:
        with open(_STAMP) as f:
            current = os.path.exists(_SO) and f.read().strip() == digest
    except FileNotFoundError:
        current = False
    if not current:
        if not _build():
            return None
        with open(_STAMP, "w") as f:
            f.write(digest)
    _lib = _NativeLib(ctypes.CDLL(_SO))
    return _lib
