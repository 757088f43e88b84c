"""Golden parity: ClipBpeTokenizer vs transformers.CLIPTokenizer.

No CLIP checkpoint exists in this environment, so the test *trains* a tiny
CLIP-layout BPE vocab (256 byte symbols + 256 ``</w>`` variants + learned
merges + the two specials) and feeds the identical vocab.json/merges.txt files
to both implementations — this exercises the whole algorithm surface (word
pattern, byte-unicode table, merge loop, specials, padding/truncation,
cleaning) independently of any particular vocabulary.

The reference consumes the HF tokenizer via `pipe.tokenizer`
(`/root/reference/ptp_utils.py:144-150`, `/root/reference/main.py:30`);
matching it token-for-token is what makes real-checkpoint alignment
precompute (word indices, mappers) land on the same columns.
"""

import collections
import json

import pytest

from p2p_tpu.utils.tokenizer import ClipBpeTokenizer, _bytes_to_unicode

transformers = pytest.importorskip("transformers")


CORPUS = (
    "a photo of a cat sitting on a mat a painting of a squirrel eating "
    "a burger the quick brown fox jumps over the lazy dog a fantasy "
    "landscape with mountains children's drawing of a bike don't stop "
    "white silver jewelry cake birthday car street snow winter"
).split()


def _train_tiny_bpe(corpus, n_merges=150):
    """Greedy most-frequent-pair BPE over a word corpus, CLIP token layout."""
    words = [tuple(w[:-1]) + (w[-1] + "</w>",) for w in corpus]
    merges = []
    for _ in range(n_merges):
        pairs = collections.Counter()
        for w in words:
            for i in range(len(w) - 1):
                pairs[(w[i], w[i + 1])] += 1
        if not pairs:
            break
        best = pairs.most_common(1)[0][0]
        merges.append(best)
        new_words = []
        for w in words:
            out, i = [], 0
            while i < len(w):
                if i < len(w) - 1 and (w[i], w[i + 1]) == best:
                    out.append(w[i] + w[i + 1])
                    i += 2
                else:
                    out.append(w[i])
                    i += 1
            new_words.append(tuple(out))
        words = new_words

    byte_syms = list(_bytes_to_unicode().values())
    vocab = {}
    for s in byte_syms:
        vocab[s] = len(vocab)
    for s in byte_syms:
        vocab[s + "</w>"] = len(vocab)
    for a, b in merges:
        if a + b not in vocab:
            vocab[a + b] = len(vocab)
    vocab["<|startoftext|>"] = len(vocab)
    vocab["<|endoftext|>"] = len(vocab)
    return vocab, merges


@pytest.fixture(scope="module")
def tok_pair(tmp_path_factory):
    d = tmp_path_factory.mktemp("clip_vocab")
    vocab, merges = _train_tiny_bpe(CORPUS)
    (d / "vocab.json").write_text(json.dumps(vocab))
    (d / "merges.txt").write_text(
        "#version: 0.2\n" + "\n".join(f"{a} {b}" for a, b in merges) + "\n")
    hf = transformers.CLIPTokenizer(str(d / "vocab.json"), str(d / "merges.txt"))
    ours = ClipBpeTokenizer.from_dir(str(d))
    return hf, ours


PROMPTS = [
    "a photo of a cat",
    "A Photo OF a CAT  ",
    "the quick brown fox jumps over the lazy dog",
    "children's drawing, don't stop!",
    "squirrel-burger... 42 tokens?",
    "white silver jewelry: cake & birthday",
    "naïve café résumé",            # accented chars, OOV for the tiny vocab
    "日本語のテキスト",               # CJK: HF space-pads each ideograph
    "emoji 🙂 test",
    "tabs\tand\nnewlines\r mixed",
    "",
    "   ",
    "word " * 100,                  # forces truncation at 77
]


@pytest.mark.parametrize("text", PROMPTS, ids=range(len(PROMPTS)))
def test_encode_matches_hf(tok_pair, text):
    hf, ours = tok_pair
    got = ours(text, max_length=77)["input_ids"][0]
    want = hf(text, padding="max_length", max_length=77,
              truncation=True)["input_ids"]
    assert got == want


SPECIAL_PROMPTS = [
    "<|endoftext|>",
    "<|startoftext|>a cat<|endoftext|>",
    "a<|endoftext|>b",
    "!<|endoftext|>?",
    "<|ENDOFTEXT|> cat",            # lower-cased to the special, as in HF
    "<|endof\x00text|>",            # control char dropped after the split
    "<|endoftext",
]


@pytest.mark.parametrize("text", SPECIAL_PROMPTS,
                         ids=range(len(SPECIAL_PROMPTS)))
def test_special_tokens_in_text_match_hf(tok_pair, text):
    """A special token typed into a prompt maps to its id, as HF's added-
    token split does, and the text around it is tokenized on its own."""
    hf, ours = tok_pair
    assert ours.encode(text) == hf(text)["input_ids"]


def test_unpadded_encode_matches_hf(tok_pair):
    hf, ours = tok_pair
    for text in PROMPTS[:6]:
        assert ours.encode(text) == hf(text)["input_ids"]


def test_oov_does_not_raise(tok_pair):
    """OOV subwords must map to unk, not raise KeyError."""
    hf, ours = tok_pair
    text = "zzzzqqqq日ß"
    got = ours.encode(text)
    want = hf(text)["input_ids"]
    assert got == want


def test_per_token_decode_roundtrip(tok_pair):
    """decode([id]) per interior token — the surface word-index lookup uses
    (`/root/reference/ptp_utils.py:253`)."""
    hf, ours = tok_pair
    text = "a photo of a burger"
    ids = ours.encode(text)
    assert ids == hf(text)["input_ids"]
    for t in ids[1:-1]:
        assert ours.decode([t]).strip() == hf.decode([t]).strip()


def test_specials_and_padding_ids(tok_pair):
    hf, ours = tok_pair
    assert ours.bos_token_id == hf.bos_token_id
    assert ours.eos_token_id == hf.eos_token_id
    assert ours.pad_token_id == hf.pad_token_id


# ---------------------------------------------------------------------------
# BertWordPieceTokenizer vs transformers.BertTokenizer (LDM-256 text path,
# `/root/reference/ptp_utils.py:112-116`)
# ---------------------------------------------------------------------------


BERT_VOCAB = (
    "[PAD] [UNK] [CLS] [SEP] [MASK] a photo of cat dog the quick brown fox "
    "jump ##s ##ing over lazy squirrel eat burger bike don t ' . , ! ? - "
    "painting land ##scape b c d e f g h i j k l m n o p q r s u v w x y z "
    "##a ##b ##c ##d ##e ##f ##g ##h ##i ##j ##k ##l ##m ##n ##o ##p ##q "
    "##r ##t ##u ##v ##w ##x ##y ##z 日 本"
).split()


@pytest.fixture(scope="module")
def bert_pair(tmp_path_factory):
    from p2p_tpu.utils.tokenizer import BertWordPieceTokenizer

    d = tmp_path_factory.mktemp("bert_vocab")
    (d / "vocab.txt").write_text("\n".join(BERT_VOCAB) + "\n")
    hf = transformers.BertTokenizer(str(d / "vocab.txt"))
    ours = BertWordPieceTokenizer.from_dir(str(d))
    return hf, ours


BERT_PROMPTS = [
    "a photo of a cat",
    "The Quick Brown Fox JUMPS over the lazy dog",
    "jumping jumps eats",
    "don't stop!",
    "naïve café",                 # accents stripped by the uncased model
    "unknownlongword zzz",        # [UNK] fallthrough
    "日本 text",
    "punct-uation, test.",
    "",
    "word " * 100,
]


@pytest.mark.parametrize("text", BERT_PROMPTS, ids=range(len(BERT_PROMPTS)))
def test_bert_encode_matches_hf(bert_pair, text):
    hf, ours = bert_pair
    got = ours(text, max_length=77)["input_ids"][0]
    want = hf(text, padding="max_length", max_length=77,
              truncation=True)["input_ids"]
    assert got == want


BERT_SPECIAL_PROMPTS = [
    "[UNK]",
    "a[UNK]b",
    "[CLS] cat [SEP] [PAD] [MASK]",
    "[[MASK]]",
    "[unk] [Unk]",                # only the exact spelling is special
    "[UN\x00K]",                  # lower-cased before the control char goes
]


@pytest.mark.parametrize("text", BERT_SPECIAL_PROMPTS,
                         ids=range(len(BERT_SPECIAL_PROMPTS)))
def test_bert_special_tokens_in_text_match_hf(bert_pair, text):
    hf, ours = bert_pair
    assert ours.encode(text) == hf(text)["input_ids"]


def test_bert_specials(bert_pair):
    hf, ours = bert_pair
    assert ours.bos_token_id == hf.cls_token_id
    assert ours.eos_token_id == hf.sep_token_id
    assert ours.pad_token_id == hf.pad_token_id


def test_bert_per_token_decode_strips_to_word_pieces(bert_pair):
    """`get_word_inds` strips '#' from per-token decodes
    (`/root/reference/ptp_utils.py:253`) — subword pieces must decode with the
    '##' marker for length re-accumulation to work."""
    _, ours = bert_pair
    ids = ours.encode("jumping")
    pieces = [ours.decode([t]) for t in ids[1:-1]]
    assert pieces == ["jump", "##ing"]


# ---------------------------------------------------------------------------
# Hypothesis fuzz: arbitrary unicode must tokenize identically to HF
# ---------------------------------------------------------------------------

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

_text = st.text(
    alphabet=st.characters(
        codec="utf-8",
        categories=("L", "N", "P", "S", "Z", "M"),  # letters .. marks
    ),
    max_size=60,
)


@settings(max_examples=60, deadline=None)
@given(_text)
def test_clip_fuzz_matches_hf(tok_pair, text):
    hf, ours = tok_pair
    got = ours(text, max_length=77)["input_ids"][0]
    want = hf(text, padding="max_length", max_length=77,
              truncation=True)["input_ids"]
    assert got == want, repr(text)


@settings(max_examples=60, deadline=None)
@given(_text)
def test_bert_fuzz_matches_hf(bert_pair, text):
    hf, ours = bert_pair
    got = ours(text, max_length=77)["input_ids"][0]
    want = hf(text, padding="max_length", max_length=77,
              truncation=True)["input_ids"]
    assert got == want, repr(text)
