"""Ground-truth MPEG audio decoder for tests, via pygame's bundled
libmpg123 over ctypes.

The production encoder (chiaswarm_tpu/toolbox/mpeg_audio.py) was built by
black-box measurement against this decoder; these helpers let the tests
re-verify that end-to-end (encode -> real third-party decode -> SNR vs the
original PCM). Not a production dependency: `find_libmpg123()` returns
None when pygame isn't installed and the tests skip.
"""

from __future__ import annotations

import ctypes
import glob
import os

import numpy as np

_MPG123_OK = 0
_MPG123_NEW_FORMAT = -11
_MPG123_NEED_MORE = -10
_MPG123_DONE = -12
_ENC_FLOAT_32 = 0x200

_lib = None


def find_libmpg123() -> str | None:
    roots = []
    try:
        import pygame

        roots.append(os.path.join(os.path.dirname(os.path.dirname(
            pygame.__file__)), "pygame.libs"))
    except Exception:
        pass
    roots += ["/usr/lib", "/usr/lib/x86_64-linux-gnu", "/usr/local/lib"]
    for root in roots:
        hits = glob.glob(os.path.join(root, "libmpg123*so*"))
        if hits:
            return hits[0]
    return None


def _load():
    global _lib
    if _lib is None:
        path = find_libmpg123()
        if path is None:
            raise RuntimeError("libmpg123 not found")
        m = ctypes.CDLL(path)
        m.mpg123_init()
        m.mpg123_new.restype = ctypes.c_void_p
        m.mpg123_new.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_int)]
        m.mpg123_open_feed.argtypes = [ctypes.c_void_p]
        m.mpg123_feed.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
        m.mpg123_read.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t)]
        m.mpg123_getformat.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        m.mpg123_format_none.argtypes = [ctypes.c_void_p]
        m.mpg123_format.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_int, ctypes.c_int]
        m.mpg123_delete.argtypes = [ctypes.c_void_p]
        _lib = m
    return _lib


def decode(data: bytes) -> tuple[np.ndarray, int]:
    """MPEG audio stream -> (float32 PCM [n, ch], sample rate)."""
    m = _load()
    err = ctypes.c_int()
    handle = m.mpg123_new(None, ctypes.byref(err))
    if not handle:
        raise RuntimeError(f"mpg123_new failed: {err.value}")
    try:
        m.mpg123_format_none(handle)
        for r in (8000, 11025, 12000, 16000, 22050, 24000,
                  32000, 44100, 48000):
            m.mpg123_format(handle, r, 3, _ENC_FLOAT_32)
        if m.mpg123_open_feed(handle) != _MPG123_OK:
            raise RuntimeError("mpg123_open_feed failed")
        if m.mpg123_feed(handle, data, len(data)) != _MPG123_OK:
            raise RuntimeError("mpg123_feed failed")
        out = bytearray()
        buf = ctypes.create_string_buffer(65536)
        done = ctypes.c_size_t()
        rate = channels = None
        while True:
            rc = m.mpg123_read(handle, buf, 65536, ctypes.byref(done))
            out += buf.raw[: done.value]
            if rc == _MPG123_NEW_FORMAT:
                r = ctypes.c_long()
                c = ctypes.c_int()
                e = ctypes.c_int()
                m.mpg123_getformat(
                    handle, ctypes.byref(r), ctypes.byref(c), ctypes.byref(e))
                rate, channels = r.value, c.value
                if e.value != _ENC_FLOAT_32:
                    raise RuntimeError(f"unexpected encoding {e.value}")
            elif rc in (_MPG123_NEED_MORE, _MPG123_DONE):
                break
            elif rc != _MPG123_OK:
                raise RuntimeError(f"mpg123_read rc={rc}")
        pcm = np.frombuffer(bytes(out), np.float32)
        if channels and channels > 1:
            pcm = pcm.reshape(-1, channels)
        else:
            pcm = pcm.reshape(-1, 1)
        if rate is None:
            raise RuntimeError("no format event (not an MPEG stream?)")
        return pcm, rate
    finally:
        m.mpg123_delete(handle)


def roundtrip_snr_db(original: np.ndarray, decoded: np.ndarray) -> float:
    """Align by cross-correlation (filterbank delay) and return SNR dB."""
    x = np.asarray(original, np.float64).ravel()
    y = np.asarray(decoded, np.float64).ravel()
    n = min(len(x), len(y))
    # the decoder's delay is the lag 0..1024 at which y best matches x.
    # y is zero-padded to n + 1024 (the decoded stream ends a few hundred
    # samples after x does), so "valid" is exactly those 1025 lags
    head = y[: n + 1024]
    head = np.pad(head, (0, n + 1024 - len(head)))
    delay = int(np.argmax(np.abs(np.correlate(head, x[:n], "valid"))))
    m = min(len(x), len(y) - delay) - 1200
    if m <= 0:
        return float("-inf")
    xs = x[600: 600 + m - 600]
    ys = y[delay + 600: delay + 600 + len(xs)]
    gain = np.dot(ys, xs) / max(np.dot(xs, xs), 1e-12)
    err = ys / (gain if abs(gain) > 1e-6 else 1.0) - xs
    return float(10 * np.log10(
        np.sum(xs ** 2) / max(np.sum(err ** 2), 1e-20)))
