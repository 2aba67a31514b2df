"""ctypes binding for the native C++ data-path library (csrc/dataloader.cpp).

Build model: the shared library is compiled on demand with g++ (cached next
to the source; pybind11 is not in this image, so the C ABI + ctypes is the
binding). Everything degrades gracefully: if no compiler is available the
callers fall back to the HF tokenizer / numpy collate paths.

`NativeBPE` self-verifies on construction: it encodes a battery of probe
texts with both the native encoder and the HF tokenizer and refuses to load
(raises) on any mismatch — the compact Unicode tables in the C++ scanner
cover common scripts, and this check catches any corpus where that matters.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
from typing import List, Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc", "dataloader.cpp")
_LIB = os.path.join(os.path.dirname(_SRC), "libdistdata.so")

_lib = None
_lib_err: Optional[str] = None

PROBE_TEXTS = [
    "Nice to meet you, it's a test",
    "hello   world\n\nnew  paragraph",
    "don't you'll we've I'm he'd they're",
    "numbers 123 45.67 8,900 and (punct)!?;:--\"quotes\"",
    " leading and trailing  ",
    "tabs\tand\nnewlines \n mixed",
    "CamelCase ALLCAPS mIxEd",
    "unicode: café naïve über буквы",
    "",
    "a",
]


def _build() -> Optional[str]:
    """Compile the shared library if needed; returns an error string or None."""
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return None
    try:
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", _LIB],
            check=True, capture_output=True, text=True, timeout=120)
        return None
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.CalledProcessError as e:
        return f"g++ failed: {e.stderr[:500]}"
    except subprocess.TimeoutExpired:
        return "g++ timed out"


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first use; None if unavailable."""
    global _lib, _lib_err
    if _lib is not None or _lib_err is not None:
        return _lib
    _lib_err = _build()
    if _lib_err is not None:
        return None
    lib = ctypes.CDLL(_LIB)
    lib.tok_create.restype = ctypes.c_void_p
    lib.tok_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32, ctypes.c_int32]
    lib.tok_encode.restype = ctypes.c_int32
    lib.tok_encode.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char), ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
    lib.tok_free.argtypes = [ctypes.c_void_p]
    lib.collate_batch.argtypes = [ctypes.POINTER(ctypes.c_int32)] * 2 + \
        [ctypes.c_int32] * 5 + [ctypes.POINTER(ctypes.c_int32)] * 3
    lib.collate_indexed.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32)] + [ctypes.c_int32] * 6 + \
        [ctypes.POINTER(ctypes.c_int32)] * 3
    _lib = lib
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def native_status() -> str:
    """Which collate path a loader gets, for the entry points to print:
    the C++ library (built from csrc/dataloader.cpp on first use), or the
    numpy path and why."""
    if native_available():
        return "native C++ (csrc/dataloader.cpp)"
    return f"numpy (native library unavailable: {_lib_err})"


class NativeBPE:
    """Byte-level BPE encoder backed by the C++ library, loaded from a HF
    `tokenizer.json`. Construction verifies parity against the HF encoder on
    PROBE_TEXTS (+ optional caller-provided samples) and raises on mismatch."""

    def __init__(self, tokenizer_json: str, verify_against_hf: bool = True,
                 extra_probes: Optional[List[str]] = None):
        lib = get_lib()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_lib_err}")
        self._lib = lib
        spec = json.load(open(tokenizer_json))
        if spec["model"]["type"] != "BPE":
            raise ValueError(f"unsupported model type {spec['model']['type']}")
        pre = spec.get("pre_tokenizer") or {}
        self.add_prefix_space = bool(pre.get("add_prefix_space", False))

        vocab = spec["model"]["vocab"]
        # Added tokens (BOS/EOS/UNK) participate only as whole strings; the
        # encode path never produces them from text (the reference feeds
        # specials via collate, not the tokenizer). HF *does* match a
        # literal added-token string appearing in raw text, so callers must
        # route such corpora to the HF path — `added_tokens` is exposed for
        # that scan (see data.tokenizer.pre_tokenize; ADVICE r1).
        self.added_tokens = [at["content"]
                             for at in spec.get("added_tokens", [])]
        toks = list(vocab.keys())
        ids = [vocab[t] for t in toks]
        merges = spec["model"]["merges"]
        ml = [(m[0] if isinstance(m, (list, tuple)) else m.split(" ")[0])
              for m in merges]
        mr = [(m[1] if isinstance(m, (list, tuple)) else m.split(" ")[1])
              for m in merges]

        tok_arr = (ctypes.c_char_p * len(toks))(
            *[t.encode("utf-8") for t in toks])
        id_arr = (ctypes.c_int32 * len(ids))(*ids)
        ml_arr = (ctypes.c_char_p * len(ml))(*[x.encode("utf-8") for x in ml])
        mr_arr = (ctypes.c_char_p * len(mr))(*[x.encode("utf-8") for x in mr])
        unk_token = spec["model"].get("unk_token")
        unk_id = -1
        if unk_token is not None:
            unk_id = vocab.get(unk_token, -1)
            if unk_id < 0:
                for at in spec.get("added_tokens", []):
                    if at["content"] == unk_token:
                        unk_id = at["id"]
        self._tok = lib.tok_create(tok_arr, id_arr, len(toks),
                                   ml_arr, mr_arr, len(ml), unk_id)
        self._buf = (ctypes.c_int32 * (1 << 16))()

        if verify_against_hf:
            self._verify(tokenizer_json, (extra_probes or []) + PROBE_TEXTS)

    def _verify(self, tokenizer_json: str, probes: List[str]) -> None:
        try:
            from tokenizers import Tokenizer as HFTokenizer
        except ImportError:
            return  # nothing to verify against
        hf = HFTokenizer.from_file(tokenizer_json)
        for text in probes:
            if self.encode(text) != hf.encode(text).ids:
                raise RuntimeError(
                    f"native BPE disagrees with HF tokenizers on {text!r}; "
                    f"use the HF path for this corpus")

    def encode(self, text: str) -> List[int]:
        data = text.encode("utf-8")
        # explicit byte length: embedded NULs must not truncate (c_char_p
        # marshalling would stop at the first NUL)
        aps = 1 if self.add_prefix_space else 0
        n = self._lib.tok_encode(self._tok, data, len(data), aps,
                                 self._buf, len(self._buf))
        while n > len(self._buf):  # buffer too small: grow and re-encode
            self._buf = (ctypes.c_int32 * (2 * n))()
            n = self._lib.tok_encode(self._tok, data, len(data), aps,
                                     self._buf, len(self._buf))
        return list(self._buf[:n])

    def __del__(self):
        if getattr(self, "_tok", None) and getattr(self, "_lib", None):
            self._lib.tok_free(self._tok)


def native_collate(batch: List[List[int]], bos: int, eos: int,
                   ignore_idx: int, width: Optional[int] = None) -> dict:
    """C++ collate with the reference's exact semantics
    (`/root/reference/dataset.py:40-55`); same output dict as
    data.dataset.collate. `width=None` pads to the longest row + 1, the same
    default rule as `collate(pad_to=None)`."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_lib_err}")
    import itertools

    n = len(batch)
    lens_py = list(map(len, batch))
    longest = max(lens_py, default=0)
    if width is None:
        width = longest + 1
    assert width >= longest + 1, (
        f"pad width {width} < longest sequence + 1 ({longest + 1}); callers "
        f"must truncate to width-1 first (dataset.TokenDataset does)")
    flat = np.fromiter(itertools.chain.from_iterable(batch), np.int32,
                       sum(lens_py))
    lens = np.asarray(lens_py, np.int32)
    input_ids = np.empty((n, width), np.int32)
    target_ids = np.empty((n, width), np.int32)
    position_ids = np.empty((n, width), np.int32)
    as_p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    lib.collate_batch(as_p(flat), as_p(lens), n, width, bos, eos, ignore_idx,
                      as_p(input_ids), as_p(target_ids), as_p(position_ids))
    return {"input_ids": input_ids, "target_ids": target_ids,
            "position_ids": position_ids}


def native_collate_indexed(packed: np.ndarray, offsets: np.ndarray,
                           idxs: np.ndarray, cap: int, width: int,
                           bos: int, eos: int, ignore_idx: int) -> dict:
    """Whole-batch gather + truncate + collate in ONE C++ call over the
    packed corpus (csrc/dataloader.cpp::collate_indexed). `cap` is the
    maxlen-1 truncation limit TokenDataset applies; `width` the fixed pad
    length. ctypes releases the GIL for the call's duration, so a prefetch
    thread runs it concurrently with the training loop."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_lib_err}")
    assert packed.dtype == np.int32 and offsets.dtype == np.int64
    n = len(idxs)
    idxs = np.ascontiguousarray(idxs, np.int32)
    # Mirror native_collate's guard: the C++ side clamps rows to width-1
    # defensively, which would otherwise turn an undersized width into
    # silently truncated batches (ADVICE r2) instead of the error the
    # numpy path raises.
    if n:
        idx64 = idxs.astype(np.int64)
        longest = int(min((offsets[idx64 + 1] - offsets[idx64]).max(), cap))
        assert width >= longest + 1, (
            f"pad width {width} < longest selected row + 1 ({longest + 1}) "
            f"after cap {cap}")
    input_ids = np.empty((n, width), np.int32)
    target_ids = np.empty((n, width), np.int32)
    position_ids = np.empty((n, width), np.int32)
    as_p = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    lib.collate_indexed(
        as_p(packed), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        as_p(idxs), n, cap, width, bos, eos, ignore_idx,
        as_p(input_ids), as_p(target_ids), as_p(position_ids))
    return {"input_ids": input_ids, "target_ids": target_ids,
            "position_ids": position_ids}
