"""ctypes bridge to the C++ WordPiece core (csrc/wordpiece.cpp).

Builds the shared library on first use (g++ -O2) — no pybind11 in this
image, so the ABI is plain C. The library's file name carries a hash
of the source, so a copy of the tree (which resets mtimes) can never
load a library built from other source. It is kept beside the source
unless ``load`` is given another directory. A failed build raises
OSError; ``WordPieceTokenizer`` catches it and uses the pure-Python
engine, which produces identical results (asserted by tests).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from collections import Counter
from typing import Iterable, List, Optional

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "wordpiece.cpp")
_lock = threading.Lock()
_lib = None


def load(build_dir: Optional[str] = None) -> ctypes.CDLL:
    """The loaded library, built first if ``build_dir`` (default: beside
    the source) holds none for this source. The first call decides the
    directory for the process."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        path = os.path.join(build_dir or os.path.dirname(_SRC),
                            f"libwordpiece-{digest}.so")
        if not os.path.exists(path):
            # Build to a process-unique temp path and rename into place:
            # rename is atomic, so concurrent processes (dataloader
            # workers on a cold cache) never dlopen a half-written ELF.
            tmp = f"{path}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     "-pthread", _SRC, "-o", tmp],
                    check=True, capture_output=True)
                os.replace(tmp, path)
            except subprocess.CalledProcessError as e:
                # normalize to OSError so callers' documented fallback
                # (except (ImportError, OSError)) catches compile failure
                raise OSError(
                    f"native tokenizer build failed: "
                    f"{e.stderr.decode(errors='replace')[:500]}") from e
            finally:
                if os.path.exists(tmp):
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
        lib = ctypes.CDLL(path)
        lib.wp_vocab_create.restype = ctypes.c_void_p
        lib.wp_vocab_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32]
        lib.wp_vocab_free.argtypes = [ctypes.c_void_p]
        lib.wp_encode_words.restype = ctypes.c_int32
        lib.wp_encode_words.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.wp_encode_docs.restype = None
        lib.wp_encode_docs.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.wp_encode_docs_raw.restype = None
        lib.wp_encode_docs_raw.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.wp_train.restype = ctypes.c_void_p  # manual free
        lib.wp_train.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int32, ctypes.c_int64]
        lib.wp_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class NativeVocab:
    """Vocab handle for repeated fast encodes."""

    def __init__(self, tokenizer):
        lib = load()
        self._lib = lib
        ordered = sorted(tokenizer.vocab.items(), key=lambda kv: kv[1])
        import numpy as np
        self._id_map = [i for _, i in ordered]  # dense idx -> real id
        self._id_map_np = np.asarray(self._id_map, np.int32)
        self._token_to_dense = {t: j for j, (t, _) in enumerate(ordered)}
        toks = (ctypes.c_char_p * len(ordered))(
            *[t.encode("utf-8") for t, _ in ordered])
        self._handle = lib.wp_vocab_create(toks, len(ordered))
        self._unk_dense = next(
            j for j, (t, _) in enumerate(ordered)
            if t == tokenizer.unk_token)
        self._prefix = tokenizer.prefix.encode("utf-8")
        self._max_chars = tokenizer.max_input_chars_per_word
        # ctypes releases the GIL during the C call, so the shared
        # result buffer (and its grow path) must be guarded for
        # concurrent encode() on one tokenizer instance.
        self._buf_lock = threading.Lock()
        self._buf = (ctypes.c_int32 * 4096)()

    def encode_words(self, words: List[str]) -> List[int]:
        """One FFI round-trip for a whole pre-tokenized word list."""
        payload = "\n".join(words).encode("utf-8")
        with self._buf_lock:
            buf = self._buf
            while True:
                n = self._lib.wp_encode_words(
                    self._handle, payload, len(payload), self._unk_dense,
                    self._max_chars, self._prefix, buf, len(buf))
                if n >= 0:
                    break
                buf = (ctypes.c_int32 * (len(buf) * 4))()
                self._buf = buf
            id_map = self._id_map
            return [id_map[buf[i]] for i in range(n)]

    def encode_docs_padded(self, docs_words: List[List[str]],
                           max_len: int, pad_id: int,
                           n_threads: int = 0):
        """Encode many pre-tokenized documents into a padded
        ``(n_docs, max_len)`` int32 matrix (real vocab ids, ``pad_id``
        past each document's length) plus a lengths vector, with the
        WordPiece matching split across C++ threads — the GIL is
        released for the whole call, so this is true multi-core
        tokenization of the corpus.
        """
        import numpy as np

        payloads = ["\n".join(ws).encode("utf-8") for ws in docs_words]
        offsets = np.zeros(len(payloads) + 1, np.int64)
        np.cumsum([len(p) for p in payloads], out=offsets[1:])
        blob = b"".join(payloads)
        out = np.zeros((len(payloads), max_len), np.int32)
        lengths = np.zeros(len(payloads), np.int32)
        if n_threads <= 0:
            n_threads = min(os.cpu_count() or 1, 16)
        self._lib.wp_encode_docs(
            self._handle, blob,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(payloads), self._unk_dense, self._max_chars, self._prefix,
            max_len, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_threads)
        return self._map_and_pad(out, lengths, pad_id), lengths

    def encode_docs_raw(self, texts: List[str], replaces, lowercase: bool,
                        specials: List[str], max_len: int, pad_id: int,
                        n_threads: int = 0):
        """Full-pipeline encode of raw ASCII documents (added-token
        matching, literal replaces, lowercasing, HF-Whitespace split,
        WordPiece) entirely inside threaded C++. Every text must be
        pure ASCII (empty strings are fine and yield empty rows — the
        caller's hook for routing non-ASCII documents elsewhere).
        Returns real-id ``(n, max_len)`` matrix + lengths.
        """
        import numpy as np

        payloads = [t.encode("ascii") for t in texts]
        offsets = np.zeros(len(payloads) + 1, np.int64)
        np.cumsum([len(p) for p in payloads], out=offsets[1:])
        blob = b"".join(payloads)

        find = (ctypes.c_char_p * max(len(replaces), 1))(
            *[f.encode("ascii") for f, _ in replaces] or [b""])
        repl = (ctypes.c_char_p * max(len(replaces), 1))(
            *[r.encode("ascii") for _, r in replaces] or [b""])
        sp_toks = (ctypes.c_char_p * max(len(specials), 1))(
            *[s.encode("ascii") for s in specials] or [b""])
        sp_dense = [self._token_to_dense[t] for t in specials]
        sp_ids = (ctypes.c_int32 * max(len(specials), 1))(
            *(sp_dense or [0]))

        out = np.zeros((len(payloads), max_len), np.int32)
        lengths = np.zeros(len(payloads), np.int32)
        if n_threads <= 0:
            n_threads = min(os.cpu_count() or 1, 16)
        self._lib.wp_encode_docs_raw(
            self._handle, blob,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(payloads), find, repl, len(replaces),
            1 if lowercase else 0, sp_toks, sp_ids, len(specials),
            self._unk_dense, self._max_chars, self._prefix, max_len,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n_threads)
        return self._map_and_pad(out, lengths, pad_id), lengths

    def _map_and_pad(self, dense_out, lengths, pad_id: int):
        """Dense-id matrix → real ids, with positions past each row's
        length set to ``pad_id`` — which therefore may be ANY int (e.g.
        an ignore sentinel), not just a vocab id, matching the
        pure-Python fallback."""
        import numpy as np

        real = self._id_map_np[dense_out]
        cols = np.arange(dense_out.shape[1])
        real[cols[None, :] >= lengths[:, None]] = pad_id
        return real

    def __del__(self):
        try:
            self._lib.wp_vocab_free(self._handle)
        except Exception:
            pass  # interpreter teardown: ctypes/lib may be gone; leak


def count_words(tokenizer, data: Iterable[str]) -> Counter:
    """Shared corpus word-counting (normalize → pre-tokenize → count);
    both the native and pure-Python trainers feed from this so their
    inputs can never diverge."""
    counts: Counter = Counter()
    for text in data:
        for w in tokenizer.pre_tokenize(tokenizer.normalize(text)):
            counts[w] += 1
    return counts


def native_train(tokenizer, data: Iterable[str], vocab_size: int,
                 special_tokens: List[str], min_frequency: int) -> dict:
    """Count words host-side, train merges in C++; returns vocab dict."""
    lib = load()
    items = sorted(count_words(tokenizer, data).items())  # deterministic
    words = (ctypes.c_char_p * len(items))(
        *[w.encode("utf-8") for w, _ in items])
    cts = (ctypes.c_int64 * len(items))(*[c for _, c in items])
    specials = (ctypes.c_char_p * len(special_tokens))(
        *[s.encode("utf-8") for s in special_tokens])
    ptr = lib.wp_train(words, cts, len(items), specials,
                       len(special_tokens),
                       tokenizer.prefix.encode("utf-8"),
                       vocab_size, min_frequency)
    try:
        raw = ctypes.string_at(ptr).decode("utf-8")
    finally:
        lib.wp_free(ptr)
    tokens = [t for t in raw.split("\n") if t]
    return {t: i for i, t in enumerate(tokens)}
