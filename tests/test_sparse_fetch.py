"""The sparse result fetch (scan_jnp.sparse_nonzero): one block-flag step
on the device brings back the nonzero blocks of a result plane, or the
plane whole past FETCH_CAP of them.  Either way the host must get every
nonzero element, flat index ascending, with its value, and the counters
must say which way it went."""

import numpy as np
import pytest

import jax.numpy as jnp

from distributed_grep_tpu.ops import scan_jnp
from distributed_grep_tpu.ops.engine import GrepEngine
from distributed_grep_tpu.runtime import explain as explain_mod
from distributed_grep_tpu.utils import spans

B, CAP = scan_jnp.FETCH_BLOCK, scan_jnp.FETCH_CAP


def _blocks_plane(n_blocks: int, seed: int) -> np.ndarray:
    """A (w, 8, 128) uint32 word plane with CAP + 8 blocks, exactly
    ``n_blocks`` of them holding one nonzero word each (anywhere in the
    block), the rest zero."""
    rng = np.random.default_rng(seed)
    flat = np.zeros((CAP + 8) * B, np.uint32)
    which = np.sort(rng.choice(CAP + 8, n_blocks, replace=False))
    flat[which * B + rng.integers(0, B, n_blocks)] = rng.integers(
        1, 2**32, n_blocks, dtype=np.uint64).astype(np.uint32)
    return flat.reshape(-1, 8, 128)


def _sparse(shape, dtype, seed: int, share: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    top = np.iinfo(dtype).max
    vals = rng.integers(1, top, shape, dtype=np.uint64, endpoint=True)
    return np.where(rng.random(shape) < share, vals, 0).astype(dtype)


def _one_at(shape, dtype, pos: int) -> np.ndarray:
    a = np.zeros(shape, dtype)
    a.reshape(-1)[pos] = np.iinfo(dtype).max
    return a


WORDS = (4, 8, 128)  # a Pallas word plane: a multiple of B elements
LANE_BYTES = (64, 40)  # an XLA lane-bytes plane: 2560 = 20 blocks
ODD_BYTES = (100, 13)  # 1300 elements: a tail block of 20
PLANES = {
    # name: plane
    "words_empty": np.zeros(WORDS, np.uint32),
    "words_first": _one_at(WORDS, np.uint32, 0),
    "words_last": _one_at(WORDS, np.uint32, -1),
    "words_sparse": _sparse(WORDS, np.uint32, 1, 0.003),
    "words_full": _sparse(WORDS, np.uint32, 2, 1.0),
    "lane_bytes_empty": np.zeros(LANE_BYTES, np.uint8),
    "lane_bytes_first": _one_at(LANE_BYTES, np.uint8, 0),
    "lane_bytes_last": _one_at(LANE_BYTES, np.uint8, -1),
    "lane_bytes_sparse": _sparse(LANE_BYTES, np.uint8, 3, 0.01),
    "lane_bytes_full": _sparse(LANE_BYTES, np.uint8, 4, 1.0),
    "odd_bytes_empty": np.zeros(ODD_BYTES, np.uint8),
    "odd_bytes_first": _one_at(ODD_BYTES, np.uint8, 0),
    "odd_bytes_last": _one_at(ODD_BYTES, np.uint8, -1),
    "odd_bytes_sparse": _sparse(ODD_BYTES, np.uint8, 5, 0.02),
    "odd_bytes_full": _sparse(ODD_BYTES, np.uint8, 6, 1.0),
    "blocks_at_cap": _blocks_plane(CAP, 7),
    "blocks_past_cap": _blocks_plane(CAP + 1, 8),
    "blocks_all": _sparse((CAP + 8, 8, 16), np.uint32, 9, 1.0),
}


@pytest.mark.parametrize("name", sorted(PLANES))
def test_fetch_gives_every_nonzero_element(name):
    plane = PLANES[name]
    counts: dict = {}
    idx, vals = scan_jnp.sparse_nonzero(jnp.asarray(plane), counts)
    flat = plane.reshape(-1)
    want = np.flatnonzero(flat)
    assert idx.dtype == np.int64
    assert np.array_equal(idx, want)
    assert vals.dtype == plane.dtype and np.array_equal(vals, flat[want])
    padded = np.zeros(-(-flat.size // B) * B, flat.dtype)
    padded[: flat.size] = flat
    n_blocks = int(np.count_nonzero(padded.reshape(-1, B).any(axis=1)))
    assert counts == {"fetch_blocks": n_blocks,
                      "fetch_overflows": int(n_blocks > CAP)}


@pytest.fixture(params=[CAP, 2], ids=["fits", "overflows"])
def fetch_cap(request, monkeypatch):
    """FETCH_CAP as is, and at 2 blocks so that a small scan's planes
    overflow; the fetch step is traced again under each."""
    monkeypatch.setattr(scan_jnp, "FETCH_CAP", request.param)
    scan_jnp.fetch_nonzero_blocks.clear_cache()
    yield request.param
    monkeypatch.undo()
    scan_jnp.fetch_nonzero_blocks.clear_cache()


def test_explain_reports_fetch_counters_per_mode(fetch_cap, monkeypatch):
    """An interpret-mode device scan puts its fetch counters on its
    scan:<mode> span, and explain's engine_modes row sums them; they
    agree with the nonzero blocks of the planes the scan fetched."""
    planes: list[np.ndarray] = []
    fetch = scan_jnp.sparse_nonzero

    def spy(plane, counts=None):
        planes.append(np.array(plane).reshape(-1))
        return fetch(plane, counts)

    monkeypatch.setattr(scan_jnp, "sparse_nonzero", spy)
    eng = GrepEngine("needle", backend="device", interpret=True)
    data = b"hay\nneedle here\nhay and straw\n" * 300
    buf = spans.SpanBuffer()
    with spans.task_context(buf, job="j", worker=0, task=1, attempt="a",
                            kind="map"):
        res = eng.scan(data)
    assert res.n_matches == 300
    blocks = [int(np.count_nonzero(
        np.pad(p, (0, -p.size % B)).reshape(-1, B).any(axis=1)))
        for p in planes]
    assert planes and sum(blocks) > 2
    row = explain_mod.summarize_events(buf.drain())["modes"][eng.mode]
    assert row["fetch_blocks"] == sum(blocks)
    assert row["fetch_overflows"] == sum(b > fetch_cap for b in blocks)
    assert row["fetch_overflows"] == (len(planes) if fetch_cap == 2 else 0)
