"""Tokenizer protocol and implementations.

The reference uses the HuggingFace CLIP tokenizer (`/root/reference/main.py:30`)
purely through three operations: `encode(text) -> [ids]` (with BOS/EOS),
per-token `decode([id]) -> str` (used by word-index lookup,
`/root/reference/ptp_utils.py:253`), and fixed-length padding to 77 tokens.
We define that surface as a small protocol so the alignment / controller
precompute layer is tokenizer-agnostic:

- ``ClipBpeTokenizer`` — a self-contained CLIP byte-pair-encoding tokenizer
  that loads ``vocab.json`` + ``merges.txt`` from a local checkpoint directory
  (no network access required at runtime).
- ``HashWordTokenizer`` — a deterministic, vocab-free word tokenizer used by
  tests and random-weight benchmarks: every whitespace word maps to a stable
  id; longer words may split into multiple sub-tokens to exercise the
  multi-token alignment paths.
"""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import unicodedata
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Protocol, Sequence, Tuple


class Tokenizer(Protocol):
    """The minimal tokenizer surface the framework depends on."""

    bos_token_id: int
    eos_token_id: int
    model_max_length: int

    def encode(self, text: str) -> List[int]:
        """Tokenize to ids, including BOS and EOS (unpadded)."""
        ...

    def decode(self, ids: Sequence[int]) -> str:
        """Inverse of encode for a list of ids (special tokens included)."""
        ...


def pad_ids(ids: Sequence[int], max_length: int, pad_id: int) -> List[int]:
    """Pad/truncate to ``max_length``; truncation keeps EOS as the final token
    (mirrors HF ``padding='max_length', truncation=True`` as used at
    `/root/reference/ptp_utils.py:144-150`)."""
    ids = list(ids)
    if len(ids) > max_length:
        ids = ids[: max_length - 1] + [ids[-1]]
    return ids + [pad_id] * (max_length - len(ids))


def token_strings(tokenizer: Tokenizer, text: str) -> List[str]:
    """Per-token decoded strings for the interior (non-special) tokens.

    Matches ``[tokenizer.decode([t]).strip('#') for t in encode(text)][1:-1]``
    at `/root/reference/ptp_utils.py:253`, additionally stripping the CLIP
    end-of-word marker ``</w>`` so accumulated lengths line up with the raw
    words (the HF CLIP tokenizer's decode already drops it; ours keeps the
    marker internally for exact round-trips).
    """
    ids = tokenizer.encode(text)[1:-1]
    out = []
    for tok in ids:
        s = tokenizer.decode([tok]).strip("#").replace("</w>", "").strip()
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# HashWordTokenizer — deterministic, vocab-free (tests / random-weight bench)
# ---------------------------------------------------------------------------


@dataclass
class HashWordTokenizer:
    """Deterministic word-level tokenizer with optional sub-word splitting.

    Words hash into ``[num_special, vocab_size)``; words longer than
    ``split_len`` are split into chunks so that multi-token words exist (the
    alignment code's interesting cases — `/root/reference/seq_aligner.py:169`
    — need them). Decoding is exact via a reverse map that is populated on
    encode; unknown ids decode to a stable placeholder.
    """

    vocab_size: int = 49408
    model_max_length: int = 77
    split_len: int = 8
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 1  # CLIP pads with EOS
    sequential: bool = False  # collision-free ids, first-seen order
    _reverse: Dict[int, str] = field(default_factory=dict)
    _forward: Dict[str, int] = field(default_factory=dict)

    def _piece_id(self, piece: str) -> int:
        if self.sequential:
            # Collision-free by construction: ids hand out sequentially in
            # first-seen order. Ids are stable within an instance (bench and
            # dryrun build one tokenizer and fixed prompts), not across
            # instances — use the default hash mode when cross-instance id
            # stability matters.
            rid = self._forward.get(piece)
            if rid is None:
                rid = 2 + len(self._forward)
                if rid >= self.vocab_size:
                    raise ValueError(
                        f"HashWordTokenizer vocab exhausted at {piece!r}")
                self._forward[piece] = rid
                self._reverse[rid] = piece
            return rid
        # Purely a function of the piece — ids are identical across instances
        # and encode orders. Collisions (≈50% odds only past ~260 distinct
        # pieces) fail loudly rather than silently remapping.
        h = hashlib.sha1(piece.encode("utf-8")).digest()
        rid = 2 + int.from_bytes(h[:4], "big") % (self.vocab_size - 2)
        prev = self._reverse.setdefault(rid, piece)
        if prev != piece:
            raise ValueError(
                f"HashWordTokenizer id collision: {piece!r} vs {prev!r} (id {rid}); "
                "use ClipBpeTokenizer or a larger vocab_size for this corpus."
            )
        return rid

    def _word_pieces(self, word: str) -> List[str]:
        if len(word) <= self.split_len:
            return [word]
        return [word[i : i + self.split_len] for i in range(0, len(word), self.split_len)]

    def encode(self, text: str) -> List[int]:
        ids = [self.bos_token_id]
        for word in text.lower().split():
            for piece in self._word_pieces(word):
                ids.append(self._piece_id(piece))
        ids.append(self.eos_token_id)
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        parts = []
        for i in ids:
            if i == self.bos_token_id or i == self.eos_token_id:
                continue
            parts.append(self._reverse.get(int(i), f"<unk{int(i)}>"))
        return " ".join(parts)

    def __call__(self, texts, padding: str = "max_length", max_length: Optional[int] = None,
                 truncation: bool = True):
        """HF-style batch call returning ``{'input_ids': [[int]]}``."""
        if isinstance(texts, str):
            texts = [texts]
        max_length = max_length or self.model_max_length
        batch = [pad_ids(self.encode(t), max_length, self.pad_token_id) for t in texts]
        return {"input_ids": batch}


# ---------------------------------------------------------------------------
# ClipBpeTokenizer — real CLIP BPE, loaded from local vocab files
# ---------------------------------------------------------------------------


# CLIP's word-splitting pattern (public, from the CLIP paper's released code).
# Prefer the `regex` module for true Unicode classes; fall back to an
# ASCII-approximate pattern when only stdlib `re` is available (non-ASCII
# words then split per-character — fine for the hash tokenizer / tests, but
# real-checkpoint use should have `regex` installed).
try:
    import regex as _re_mod

    _CLIP_PAT = _re_mod.compile(
        r"<\|startoftext\|>|<\|endoftext\|>|'s|'t|'re|'ve|'m|'ll|'d|[\p{L}]+|[\p{N}]|[^\s\p{L}\p{N}]+",
        _re_mod.IGNORECASE,
    )
except ImportError:  # pragma: no cover
    import re as _re_mod

    _CLIP_PAT = _re_mod.compile(
        r"<\|startoftext\|>|<\|endoftext\|>|'s|'t|'re|'ve|'m|'ll|'d|[a-zA-Z]+|[0-9]|[^\sa-zA-Z0-9]+",
        _re_mod.IGNORECASE,
    )


def _strip_controls_pad_cjk(text: str) -> str:
    """Shared normalization pre-pass (HF BasicTokenizer semantics): drop
    control chars / U+FFFD, space-pad CJK ideographs, fold whitespace chars
    to plain spaces. Used by both the CLIP and BERT tokenizers — keep in one
    place so Unicode edge-case fixes can't diverge."""
    out = []
    for ch in text:
        cp = ord(ch)
        if cp == 0 or cp == 0xFFFD or (unicodedata.category(ch).startswith("C")
                                       and ch not in "\t\n\r"):
            continue
        if _is_cjk(cp):
            out.append(f" {ch} ")
        elif ch in "\t\n\r" or unicodedata.category(ch) == "Zs":
            out.append(" ")
        else:
            out.append(ch)
    return "".join(out)


def _split_on_specials(text: str, specials: Sequence[str]) -> List[str]:
    """Split ``text`` around exact (case-sensitive) occurrences of the special
    tokens, keeping them as their own pieces and dropping empty pieces.

    HF's slow tokenizers do this on the raw text before any normalization
    (``tokens_trie.split``), so a special token typed into a prompt maps to
    its id and the text on either side of it is tokenized on its own."""
    if not specials:
        return [text] if text else []
    pat = "(" + "|".join(_re_mod.escape(s) for s in
                         sorted(specials, key=len, reverse=True)) + ")"
    return [p for p in _re_mod.split(pat, text) if p]


def _is_cjk(cp: int) -> bool:
    """CJK ideograph ranges (the set HF's BasicTokenizer space-pads)."""
    return (
        0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF
        or 0x20000 <= cp <= 0x2A6DF or 0x2A700 <= cp <= 0x2B73F
        or 0x2B740 <= cp <= 0x2B81F or 0x2B820 <= cp <= 0x2CEAF
        or 0xF900 <= cp <= 0xFAFF or 0x2F800 <= cp <= 0x2FA1F
    )


def _bytes_to_unicode() -> Dict[int, str]:
    """GPT-2/CLIP reversible byte→unicode table (standard public algorithm)."""
    bs = (
        list(range(ord("!"), ord("~") + 1))
        + list(range(ord("\xa1"), ord("\xac") + 1))
        + list(range(ord("\xae"), ord("\xff") + 1))
    )
    cs = bs[:]
    n = 0
    for b in range(256):
        if b not in bs:
            bs.append(b)
            cs.append(256 + n)
            n += 1
    return dict(zip(bs, [chr(c) for c in cs]))


def _get_pairs(word: Tuple[str, ...]):
    return {(word[i], word[i + 1]) for i in range(len(word) - 1)}


class ClipBpeTokenizer:
    """CLIP's lower-cased byte-level BPE, loading vocab/merges from disk.

    Point it at a local ``tokenizer/`` directory of an SD checkpoint
    (``vocab.json`` + ``merges.txt``); nothing is fetched from the network.
    """

    def __init__(self, vocab_path: str, merges_path: str, model_max_length: int = 77):
        with open(vocab_path, "r", encoding="utf-8") as f:
            self.encoder: Dict[str, int] = json.load(f)
        self.decoder = {v: k for k, v in self.encoder.items()}
        opener = gzip.open if merges_path.endswith(".gz") else open
        with opener(merges_path, "rt", encoding="utf-8") as f:
            merges = f.read().split("\n")
        merges = [tuple(m.split()) for m in merges if m and not m.startswith("#version")]
        self.bpe_ranks = dict(zip(merges, range(len(merges))))
        self.byte_encoder = _bytes_to_unicode()
        self.byte_decoder = {v: k for k, v in self.byte_encoder.items()}
        self.specials = [s for s in ("<|startoftext|>", "<|endoftext|>")
                         if s in self.encoder]
        # A special spelled in other case survives to the word pattern after
        # lower-casing; HF's BPE cache is seeded so it stays one token.
        self.cache: Dict[str, str] = {s: s for s in self.specials}
        self.model_max_length = model_max_length
        self.bos_token_id = self.encoder.get("<|startoftext|>", 49406)
        self.eos_token_id = self.encoder.get("<|endoftext|>", 49407)
        self.pad_token_id = self.eos_token_id

    @classmethod
    def from_dir(cls, path: str, **kw) -> "ClipBpeTokenizer":
        return cls(os.path.join(path, "vocab.json"), os.path.join(path, "merges.txt"), **kw)

    def _bpe(self, token: str) -> str:
        if token in self.cache:
            return self.cache[token]
        word = tuple(token[:-1]) + (token[-1] + "</w>",)
        pairs = _get_pairs(word)
        if not pairs:
            return token + "</w>"
        while True:
            bigram = min(pairs, key=lambda p: self.bpe_ranks.get(p, float("inf")))
            if bigram not in self.bpe_ranks:
                break
            first, second = bigram
            new_word: List[str] = []
            i = 0
            while i < len(word):
                try:
                    j = word.index(first, i)
                except ValueError:
                    new_word.extend(word[i:])
                    break
                new_word.extend(word[i:j])
                i = j
                if i < len(word) - 1 and word[i] == first and word[i + 1] == second:
                    new_word.append(first + second)
                    i += 2
                else:
                    new_word.append(word[i])
                    i += 1
            word = tuple(new_word)
            if len(word) == 1:
                break
            pairs = _get_pairs(word)
        out = " ".join(word)
        self.cache[token] = out
        return out

    def _basic_clean(self, text: str) -> List[str]:
        """Normalize exactly as ``transformers.CLIPTokenizer`` does without
        ftfy (its BasicTokenizer path, strip_accents=False,
        do_split_on_punc=False): drop control chars, space-pad CJK ideographs,
        NFC-normalize, whitespace-split, lowercase. Golden-tested against the
        HF tokenizer in tests/test_tokenizer.py."""
        text = unicodedata.normalize("NFC", _strip_controls_pad_cjk(text))
        text = " ".join(w.lower() for w in text.split())
        return _CLIP_PAT.findall(text)

    def encode(self, text: str) -> List[int]:
        # OOV subwords map to the unk token (= <|endoftext|>), matching HF's
        # CLIPTokenizer unk_token default rather than raising KeyError. With a
        # full CLIP vocab (all 256 byte symbols present) this never triggers.
        unk = self.eos_token_id
        ids = [self.bos_token_id]
        for piece in _split_on_specials(text, self.specials):
            if piece in self.specials:
                ids.append(self.encoder[piece])
                continue
            for token in self._basic_clean(piece):
                token = "".join(self.byte_encoder[b] for b in token.encode("utf-8"))
                ids.extend(self.encoder.get(t, unk) for t in self._bpe(token).split(" "))
        ids.append(self.eos_token_id)
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        text = "".join(self.decoder.get(int(i), "") for i in ids)
        text = text.replace("<|startoftext|>", "").replace("<|endoftext|>", "")
        data = bytearray(self.byte_decoder[c] for c in text if c in self.byte_decoder)
        return data.decode("utf-8", errors="replace").replace("</w>", " ").strip()

    def __call__(self, texts, padding: str = "max_length", max_length: Optional[int] = None,
                 truncation: bool = True):
        if isinstance(texts, str):
            texts = [texts]
        max_length = max_length or self.model_max_length
        batch = [pad_ids(self.encode(t), max_length, self.pad_token_id) for t in texts]
        return {"input_ids": batch}


# ---------------------------------------------------------------------------
# BertWordPieceTokenizer — the LDM-256 backend's text tokenizer
# ---------------------------------------------------------------------------


class BertWordPieceTokenizer:
    """bert-base-uncased WordPiece, loading ``vocab.txt`` from disk.

    The LDM-256 pipeline tokenizes with the BERT tokenizer before its
    `model.bert` encoder (`/root/reference/ptp_utils.py:112-116`). Surface
    matches :class:`Tokenizer`: ``encode`` wraps in [CLS]/[SEP] (= bos/eos),
    pads with [PAD]=0; per-token ``decode`` yields "##"-prefixed subwords that
    the word-index lookup strips (`/root/reference/ptp_utils.py:253` does
    ``.strip("#")`` precisely for this). Normalization mirrors HF's
    BasicTokenizer for the uncased model: lower-case, strip accents, split
    punctuation, space-pad CJK. Golden-tested vs ``transformers.BertTokenizer``
    in tests/test_tokenizer.py.
    """

    def __init__(self, vocab_path: str, model_max_length: int = 77):
        self.vocab: Dict[str, int] = {}
        with open(vocab_path, "r", encoding="utf-8") as f:
            for line in f:
                tok = line.rstrip("\n")
                if tok:
                    self.vocab[tok] = len(self.vocab)
        self.ids_to_tokens = {v: k for k, v in self.vocab.items()}
        self.model_max_length = model_max_length
        self.bos_token_id = self.vocab["[CLS]"]
        self.eos_token_id = self.vocab["[SEP]"]
        self.pad_token_id = self.vocab["[PAD]"]
        self.unk_token_id = self.vocab["[UNK]"]
        self.specials = [s for s in ("[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]")
                         if s in self.vocab]
        self.max_chars_per_word = 100

    @classmethod
    def from_dir(cls, path: str, **kw) -> "BertWordPieceTokenizer":
        return cls(os.path.join(path, "vocab.txt"), **kw)

    @staticmethod
    def _is_punct(ch: str) -> bool:
        cp = ord(ch)
        if (33 <= cp <= 47 or 58 <= cp <= 64 or 91 <= cp <= 96 or 123 <= cp <= 126):
            return True
        return unicodedata.category(ch).startswith("P")

    def _basic_tokenize(self, text: str) -> List[str]:
        words = _strip_controls_pad_cjk(text).split()
        tokens: List[str] = []
        for w in words:
            w = w.lower()
            # strip accents (uncased model): NFD then drop Mn marks
            w = "".join(c for c in unicodedata.normalize("NFD", w)
                        if unicodedata.category(c) != "Mn")
            # split on punctuation, keeping each punct char as its own token
            cur = ""
            for ch in w:
                if self._is_punct(ch):
                    if cur:
                        tokens.append(cur)
                        cur = ""
                    tokens.append(ch)
                else:
                    cur += ch
            if cur:
                tokens.append(cur)
        return tokens

    def _wordpiece(self, word: str) -> List[int]:
        if len(word) > self.max_chars_per_word:
            return [self.unk_token_id]
        ids: List[int] = []
        start = 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = self.vocab[piece]
                    break
                end -= 1
            if cur is None:
                return [self.unk_token_id]  # whole word becomes [UNK]
            ids.append(cur)
            start = end
        return ids

    def encode(self, text: str) -> List[int]:
        ids = [self.bos_token_id]
        for piece in _split_on_specials(text, self.specials):
            if piece in self.specials:
                ids.append(self.vocab[piece])
                continue
            for word in self._basic_tokenize(piece):
                ids.extend(self._wordpiece(word))
        ids.append(self.eos_token_id)
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        toks = [self.ids_to_tokens.get(int(i), "[UNK]") for i in ids
                if int(i) not in (self.bos_token_id, self.eos_token_id,
                                  self.pad_token_id)]
        text = " ".join(toks).replace(" ##", "")
        return text

    def __call__(self, texts, padding: str = "max_length",
                 max_length: Optional[int] = None, truncation: bool = True):
        if isinstance(texts, str):
            texts = [texts]
        max_length = max_length or self.model_max_length
        batch = [pad_ids(self.encode(t), max_length, self.pad_token_id)
                 for t in texts]
        return {"input_ids": batch}
