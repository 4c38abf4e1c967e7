"""Property-based fuzzing of the exactness-critical paths.

The framework's central promise is byte-exact parity with the reference
semantics for ARBITRARY inputs (SURVEY.md §4's differential-oracle
discipline).  These properties throw adversarial inputs — random bytes,
pathological token shapes, hostile JSON strings — at the device kernels and
the native codec and require agreement with the trivially-correct host
implementations.
"""

import collections
import json
import os
import re

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

jax = pytest.importorskip("jax")

from dsi_tpu import native
from dsi_tpu.mr.worker import ihash
from dsi_tpu.ops.grepk import grep_host_result, is_literal_pattern
from dsi_tpu.ops.wordcount import count_words_host_result

ASCII_WORDS = re.compile(r"[A-Za-z]+")

# Text drawn from a tiny alphabet maximizes boundary collisions: runs of
# letters vs separators, words at chunk edges, token-dense pathologies.
dense_text = st.text(alphabet="ab XY.\n\t0", min_size=0, max_size=2000)
ascii_bytes = st.binary(min_size=0, max_size=1500).map(
    lambda b: bytes(x & 0x7F for x in b))


@settings(max_examples=60, deadline=None)
@given(dense_text)
def test_wordcount_kernel_matches_counter(text):
    data = text.encode("ascii")
    res = count_words_host_result(data, u_cap=256)
    assert res is not None
    want = collections.Counter(ASCII_WORDS.findall(text))
    assert {w: c for w, (c, _) in res.items()} == dict(want)
    for w, (_, h) in res.items():
        assert h == ihash(w)


@settings(max_examples=40, deadline=None)
@given(ascii_bytes)
def test_wordcount_kernel_arbitrary_ascii_bytes(data):
    res = count_words_host_result(data, u_cap=256)
    assert res is not None
    want = collections.Counter(
        ASCII_WORDS.findall(data.decode("ascii", "ignore")))
    # NUL and control bytes are non-letters for the kernel; the regex over
    # the decoded text sees the same token boundaries.
    assert {w: c for w, (c, _) in res.items()} == dict(want)


# Adversarial Unicode alphabet for tokenizer parity: ASCII letters and
# separators, Nl numeral letters (Roman numerals — "letters" to Python's \w
# but NOT to Go's unicode.IsLetter), No numerics, combining marks, CJK,
# Greek, a Latin-1 ordinal (Lo — a real letter), digits and punctuation.
unicode_text = st.text(
    alphabet="ab XY.\n0Ⅳⅻ²½ªµ漢語αβ́̈_-", min_size=0, max_size=800)


def go_letter_runs(text):
    """Rune-level oracle for strings.FieldsFunc(s, !unicode.IsLetter)
    (mrapps/wc.go:23): maximal runs of Unicode category-L code points."""
    import unicodedata

    out, cur = [], []
    for ch in text:
        if unicodedata.category(ch).startswith("L"):
            cur.append(ch)
        elif cur:
            out.append("".join(cur))
            cur = []
    if cur:
        out.append("".join(cur))
    return out


@settings(max_examples=80, deadline=None)
@given(unicode_text)
def test_tokenizer_matches_go_isletter_on_unicode(text):
    from dsi_tpu.apps.wc import tokenize

    assert tokenize(text) == go_letter_runs(text)


@settings(max_examples=40, deadline=None)
@given(unicode_text)
def test_wc_map_host_path_unicode_parity(text):
    """The full host Map (the kernel's fallback contract) must produce
    exactly the Go-semantics words on non-ASCII text too."""
    from dsi_tpu.apps import wc

    assert [kv.key for kv in wc.Map("f", text)] == go_letter_runs(text)


@settings(max_examples=40, deadline=None)
@given(dense_text, st.text(alphabet="abX .", min_size=1, max_size=6))
def test_grep_kernel_matches_regex(text, pat):
    data = text.encode("ascii")
    got = grep_host_result(data, pat)
    if not is_literal_pattern(pat):
        assert got is None
        return
    want = [line for line in text.split("\n") if pat in line]
    assert got == want


json_strings = st.text(min_size=0, max_size=50)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(json_strings, json_strings), max_size=30))
def test_native_codec_never_diverges(tmp_path_factory, records):
    if not native.available():
        pytest.skip("native toolchain unavailable")
    d = tmp_path_factory.mktemp("kv")
    path = os.path.join(str(d), "kv")
    with open(path, "w") as f:
        for k, v in records:
            try:
                f.write(json.dumps({"Key": k, "Value": v}) + "\n")
            except (ValueError, UnicodeEncodeError):
                return  # unencodable (should not happen for str)
    nat = native.decode_kv_file(path)
    py = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                break
            py.append((obj["Key"], obj["Value"]))
    # native either agrees exactly or declines
    assert nat is None or nat == py


# ---- the whole-corpus single-program path (ops/corpus_wc.py) ----

from dsi_tpu.ops.corpus_wc import corpus_wordcount  # noqa: E402

corpus_lists = st.lists(dense_text, min_size=0, max_size=5)


def _longest_run(texts):
    return max((len(w) for t in texts for w in ASCII_WORDS.findall(t)),
               default=0)


@settings(max_examples=40, deadline=None)
@given(corpus_lists, st.booleans())
def test_corpus_wordcount_matches_counter(texts, pack6):
    raws = [t.encode("ascii") for t in texts]
    res = corpus_wordcount(raws, piece_size=1 << 12, u_cap=256, pack6=pack6)
    if _longest_run(texts) > 64:
        assert res is None  # documented escape: host path handles it
        return
    assert res is not None
    want = collections.Counter()
    for t in texts:
        want.update(ASCII_WORDS.findall(t))
    got = {w: c for w, (c, _) in res.to_dict().items()}
    assert got == dict(want)
    # Partition ids must be the reference ihash (mr/worker.go:33-37,76).
    for w, (_, part) in res.to_dict().items():
        assert part == ihash(w) % 10


@settings(max_examples=30, deadline=None)
@given(st.binary(min_size=1, max_size=1500))
def test_corpus_wordcount_arbitrary_bytes_exact_or_declines(data):
    res = corpus_wordcount([data], piece_size=1 << 12, u_cap=256)
    if any(b >= 0x80 for b in data) or _longest_run(
            [data.decode("latin-1")]) > 64:
        assert res is None  # non-ASCII or >64-byte word: host path decides
        return
    assert res is not None
    want = collections.Counter(ASCII_WORDS.findall(data.decode("ascii")))
    got = {w: c for w, (c, _) in res.to_dict().items()}
    assert got == dict(want)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.text(alphabet="kq vw,", min_size=0, max_size=300),
                min_size=1, max_size=4))
def test_corpus_output_files_match_oracle_lines(tmp_path_factory, texts):
    from dsi_tpu.ops.corpus_wc import write_corpus_output

    tmp = tmp_path_factory.mktemp("fuzzout")
    raws = [t.encode() for t in texts]
    res = corpus_wordcount(raws, piece_size=1 << 12, u_cap=256)
    if _longest_run(texts) > 64:
        assert res is None
        return
    write_corpus_output(res, 10, str(tmp))
    got = []
    for r in range(10):
        with open(tmp / f"mr-out-{r}", encoding="utf-8") as f:
            got.extend(l for l in f if l.strip())
    want = collections.Counter()
    for t in texts:
        want.update(ASCII_WORDS.findall(t))
    assert sorted(got) == sorted(f"{w} {c}\n" for w, c in want.items())


# ---- the native map-side encoder (partition + escape + serialize) ----

kv_text = st.text(min_size=0, max_size=60)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(kv_text, kv_text), min_size=0, max_size=40),
       st.integers(min_value=1, max_value=12))
def test_native_encoder_blobs_roundtrip_and_partition(tmp_path_factory,
                                                      pairs, n_reduce):
    if not native.available():
        pytest.skip("native toolchain unavailable")
    from dsi_tpu.mr.types import KeyValue

    kva = [KeyValue(k, v) for k, v in pairs]
    blobs = native.encode_partitions(kva, n_reduce)
    # st.text never generates surrogates, so None here could only be an
    # unexpected native failure — a silent pass would mask it.
    assert blobs is not None
    seen = []
    for r, blob in enumerate(blobs):
        # Split on \n only — the format's record delimiter (splitlines()
        # would also split on U+0085/U+2028 INSIDE raw-UTF-8 values).
        for line in blob.decode("utf-8").split("\n"):
            if not line:
                continue
            obj = json.loads(line)
            assert ihash(obj["Key"]) % n_reduce == r
            seen.append((obj["Key"], obj["Value"]))
    assert sorted(seen) == sorted(pairs)


@pytest.mark.parametrize("trial", range(6))
def test_fuzz_grouper_shapes_match_counter(trial):
    """Random shapes, vocabularies and capacities (3 to 3,000 words, 50
    to 20,000 tokens, a first capacity the vocabulary may overflow)
    through the kernel and its retry ladder, against the host
    ``Counter`` and ``ihash``."""
    import random
    import string

    rng = random.Random(99 + trial)
    n_vocab = rng.choice([3, 40, 500, 3000])
    words = ["".join(rng.choices(string.ascii_letters,
                                 k=rng.randint(1, 14)))
             for _ in range(n_vocab)]
    n_tokens = rng.choice([50, 2000, 20000])
    text = " ".join(rng.choice(words) for _ in range(n_tokens))
    u_cap = rng.choice([1 << 8, 1 << 12])
    res = count_words_host_result(text.encode(), u_cap=u_cap)
    assert res is not None, (n_vocab, n_tokens, u_cap)
    want = collections.Counter(ASCII_WORDS.findall(text))
    assert {w: c for w, (c, _) in res.items()} == dict(want)
    for w, (_, h) in res.items():
        assert h == ihash(w)


# ---- checkpoint snapshot round-trips (dsi_tpu/ckpt + device services) ----
#
# The crash-resume property reduced to its serialization core: an
# ARBITRARY service state, imaged by checkpoint_state(), pushed through
# the real durable store (npz payload + CRC'd manifest on disk), and
# restored into a fresh service must drain BYTE-EQUAL to the original.
# Keys/counts are raw random bits (no decode step is involved in a
# drain), so this fuzzes the layout/dtype/sharding plumbing rather than
# tokenizer-reachable states only.

from hypothesis.extra import numpy as hnp  # noqa: E402

from dsi_tpu.ckpt import CheckpointStore  # noqa: E402
from dsi_tpu.device import (DeviceHistogram, DevicePostings,  # noqa: E402
                            DeviceTable, DeviceTopK)
from dsi_tpu.parallel.shuffle import default_mesh  # noqa: E402

_N_DEV, _CAP, _KK = 8, 8, 2


class _CaptureAcc:
    """Drain sink recording raw arrays — byte-level ground truth with
    no spelling decode in the way."""

    def __init__(self):
        self.rows = []

    def add(self, keys, lens, cnts, parts):
        self.rows.append((np.array(keys), np.array(lens),
                          np.array(cnts), np.array(parts)))

    def equal(self, other) -> bool:
        return len(self.rows) == len(other.rows) and all(
            all(np.array_equal(x, y) for x, y in zip(a, b))
            for a, b in zip(self.rows, other.rows))


def _table_img(draw):
    nrows = draw(hnp.arrays(np.int64, (_N_DEV,),
                            elements=st.integers(0, _CAP)))
    return {
        "keys": draw(hnp.arrays(np.uint32, (_N_DEV, _CAP, _KK),
                                elements=st.integers(0, 2 ** 32 - 1))),
        "lens": draw(hnp.arrays(np.int32, (_N_DEV, _CAP),
                                elements=st.integers(0, 8))),
        "cnts": draw(hnp.arrays(np.uint64, (_N_DEV, _CAP),
                                elements=st.integers(0, 2 ** 64 - 1))),
        "parts": draw(hnp.arrays(np.int32, (_N_DEV, _CAP),
                                 elements=st.integers(0, 9))),
        "tn": nrows.astype(np.int32),
        "nrows": nrows,
    }


def _roundtrip(tmpdir, svc_factory, img):
    """restore(img) -> checkpoint_state -> durable store -> restore into
    a fresh service; returns (original service, restored service)."""
    s1 = svc_factory()
    s1.restore_state(img)
    state = s1.checkpoint_state()
    store = CheckpointStore(str(tmpdir), "fuzz", {"shape": "fixed"})
    meta = {k: int(v) for k, v in state.items() if np.ndim(v) == 0}
    store.save({k: v for k, v in state.items() if np.ndim(v) > 0}, meta)
    loaded_meta, arrays = store.load_latest()
    arrays.update({k: np.array(v) for k, v in loaded_meta.items()})
    s2 = svc_factory()
    s2.restore_state(arrays)
    return s1, s2


@settings(max_examples=6, deadline=None)
@given(st.data())
def test_fuzz_device_table_snapshot_roundtrip(tmp_path_factory, data):
    mesh = default_mesh(_N_DEV)
    img = _table_img(data.draw)
    accs = []

    def factory():
        accs.append(_CaptureAcc())
        return DeviceTable(mesh, kk=_KK, cap=_CAP, acc=accs[-1])

    s1, s2 = _roundtrip(tmp_path_factory.mktemp("ck"), factory, img)
    s1.close()
    s2.close()
    assert accs[0].equal(accs[1])


@settings(max_examples=4, deadline=None)
@given(st.data())
def test_fuzz_device_topk_snapshot_roundtrip(tmp_path_factory, data):
    mesh = default_mesh(_N_DEV)
    img = _table_img(data.draw)
    accs = []

    def factory():
        accs.append(_CaptureAcc())
        return DeviceTopK(mesh, kk=_KK, cap=_CAP, k=4, acc=accs[-1])

    s1, s2 = _roundtrip(tmp_path_factory.mktemp("ck"), factory, img)
    s1.close()
    s2.close()
    assert accs[0].equal(accs[1])


@settings(max_examples=6, deadline=None)
@given(st.data())
def test_fuzz_device_postings_snapshot_roundtrip(tmp_path_factory, data):
    mesh = default_mesh(_N_DEV)
    width = _KK + 4
    m = data.draw(st.integers(0, _CAP))
    img = {
        "buf": data.draw(hnp.arrays(np.uint32, (_N_DEV, m, width),
                                    elements=st.integers(0, 2 ** 32 - 1))),
        "nrows": data.draw(hnp.arrays(np.int64, (_N_DEV,),
                                      elements=st.integers(0, m))),
        "cap": np.array(_CAP, dtype=np.int64),
    }
    sinks = []

    def factory():
        rows = []
        sinks.append(rows)
        return DevicePostings(mesh, width=width, cap=_CAP,
                              sink=lambda r, rows=rows: rows.append(
                                  np.array(r)))

    s1, s2 = _roundtrip(tmp_path_factory.mktemp("ck"), factory, img)
    s1.close()
    s2.close()
    assert len(sinks[0]) == len(sinks[1])
    assert all(np.array_equal(a, b) for a, b in zip(sinks[0], sinks[1]))


@settings(max_examples=6, deadline=None)
@given(hnp.arrays(np.uint64, (_N_DEV, 6),
                  elements=st.integers(0, 2 ** 64 - 1)))
def test_fuzz_device_histogram_snapshot_roundtrip(tmp_path_factory, state):
    mesh = default_mesh(_N_DEV)
    h1 = DeviceHistogram(mesh, slots=6)
    h1.restore_state({"hist": state})
    img = h1.checkpoint_state()
    store = CheckpointStore(str(tmp_path_factory.mktemp("ck")), "fuzz", {})
    store.save(img, {})
    _, arrays = store.load_latest()
    h2 = DeviceHistogram(mesh, slots=6)
    h2.restore_state(arrays)
    assert np.array_equal(h1.close(), h2.close())


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2 ** 64 - 1),
                          st.integers(1, 2 ** 40)), max_size=30))
def test_fuzz_keycounts_snapshot_roundtrip(pairs):
    from dsi_tpu.device import KeyCounts

    kc = KeyCounts()
    for k, c in pairs:
        kc._counts[k] = kc._counts.get(k, 0) + c
    kc2 = KeyCounts()
    kc2.restore(kc.snapshot())
    assert kc2.finalize() == kc.finalize()


# ── delta-chain restore properties (ISSUE 8) ───────────────────────────


def _chain_run(words, dacc, save_shards, resume_shards, table_cap,
               tmpdir):
    """Random fold sequence → interleaved full/delta saves (cadence 1,
    small re-base window so fulls and deltas interleave) → restore at
    EVERY seq → byte-equal final output.  GC is disabled for the run so
    every restore point stays walkable; each seq is restored from a
    pruned copy of the store (manifests above it deleted — exactly the
    on-disk state a crash right after that save leaves, modulo
    retention)."""
    import shutil

    from dsi_tpu.parallel.streaming import wordcount_streaming

    mesh = default_mesh(4)
    line = (" ".join(words) + "\n").encode()
    # >= 4 steps at 1 KB/device chunks on the 4-dev mesh, whatever the
    # drawn vocabulary's line width — a chain needs several links.
    text = line * max(4, (16 << 10) // len(line) + 1)

    def run(ck=None, resume=False, shards=0):
        return wordcount_streaming(
            [text], mesh=mesh, n_reduce=10, chunk_bytes=1 << 10,
            u_cap=256, depth=2, device_accumulate=dacc, sync_every=2,
            mesh_shards=shards if dacc else 0, checkpoint_dir=ck,
            checkpoint_every=1, checkpoint_delta=True,
            checkpoint_async=True, resume=resume)

    base = run()
    assert base is not None
    ck = os.path.join(str(tmpdir), "ck")
    gc_orig = CheckpointStore._gc
    old_env = {k: os.environ.get(k) for k in
               ("DSI_STREAM_CKPT_REBASE", "DSI_DEVICE_TABLE_CAP")}
    os.environ["DSI_STREAM_CKPT_REBASE"] = "3"
    if table_cap:
        os.environ["DSI_DEVICE_TABLE_CAP"] = str(table_cap)
    try:
        CheckpointStore._gc = lambda self: None  # keep every seq
        assert run(ck=ck, shards=save_shards) == base
        seqs = sorted(
            int(m.group(1)) for n in os.listdir(ck)
            if (m := re.match(r"^manifest-(\d{6})\.json$", n)))
        assert len(seqs) >= 3
        kinds = set()
        for n in os.listdir(ck):
            kinds.add("delta" if n.startswith("delta-") else
                      "full" if n.startswith("state-") else None)
        assert {"full", "delta"} <= kinds  # saves really interleaved
        for s in seqs:
            trunc = os.path.join(str(tmpdir), f"at{s}")
            shutil.copytree(ck, trunc)
            for n in os.listdir(trunc):
                m = re.match(r"^(?:manifest|state|delta)-(\d{6})", n)
                if m and int(m.group(1)) > s:
                    os.remove(os.path.join(trunc, n))
            assert run(ck=trunc, resume=True,
                       shards=resume_shards) == base, \
                f"restore at seq {s} diverged"
    finally:
        CheckpointStore._gc = gc_orig
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@settings(max_examples=2, deadline=None)
@given(st.data())
def test_fuzz_delta_chain_restores_at_every_seq(tmp_path_factory, data):
    words = data.draw(st.lists(
        st.text(alphabet="abcdefghijklmnopqrstuvwxyz", min_size=1,
                max_size=8), min_size=3, max_size=40, unique=True))
    dacc = data.draw(st.booleans())
    _chain_run(words, dacc=dacc, save_shards=0, resume_shards=0,
               table_cap=0, tmpdir=tmp_path_factory.mktemp("chain"))


@settings(max_examples=1, deadline=None)
@given(st.data())
def test_fuzz_delta_chain_forced_widen_and_mesh_straddle(
        tmp_path_factory, data):
    """The hostile pair the ISSUE names: a forced device-table widen
    inside the chain window (tiny capacity rung), and a
    ``--mesh-shards`` degree change straddling the deltas (saved at
    degree 2, every restore at degree 0 — the drain-path re-entry)."""
    words = data.draw(st.lists(
        st.text(alphabet="abcdefghijklmnopqrstuvwxyz", min_size=1,
                max_size=8), min_size=20, max_size=60, unique=True))
    _chain_run(words, dacc=True, save_shards=2, resume_shards=0,
               table_cap=16, tmpdir=tmp_path_factory.mktemp("straddle"))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.text(alphabet="abcdefghijklmnopqrstuvwxyzABC",
                        min_size=1, max_size=16),
                min_size=1, max_size=80),
       st.sampled_from([2, 3, 8]))
def test_fuzz_mesh_routing_partitions_exactly(words, n_shards):
    """Shard-routing invariant (ISSUE 7): for ANY key multiset, the
    on-device ``ihash(key) % n_shards`` route (ops/meshroute.py — the
    prologue of every mesh_fold_* program) partitions exactly — every
    key lands on exactly one in-range shard, duplicates agree, the
    union is the input — and matches the host ihash oracle from
    mr/worker.py byte-for-byte."""
    import functools

    from dsi_tpu.ops.meshroute import pack_host_rows, route_dest

    kk = 4  # the 16-byte word window; max_size above stays within it
    bwords = [w.encode("ascii") for w in words]
    keys, lens, oracle = pack_host_rows(bwords, n_shards, kk)
    valid = np.ones(len(bwords), dtype=bool)
    route = jax.jit(functools.partial(route_dest, n_shards=n_shards,
                                      park=n_shards))
    dest = np.asarray(route(keys, lens, valid))
    # Exact partition: every key on one in-range shard...
    assert ((dest >= 0) & (dest < n_shards)).all()
    # ...duplicates agree (ownership is a pure function of the key)...
    seen = {}
    for w, d in zip(bwords, dest.tolist()):
        assert seen.setdefault(w, d) == d
    # ...and device == host oracle (mr.worker ihash), byte-for-byte.
    assert dest.tolist() == oracle.tolist()
    for w, d in zip(words, dest.tolist()):
        assert d == ihash(w) % n_shards
    # Invalid rows park on the dump destination, never on a shard.
    none_valid = np.zeros(len(bwords), dtype=bool)
    parked = np.asarray(route(keys, lens, none_valid))
    assert (parked == n_shards).all()
