"""Batched hybrid share encryption: device KEM + host DEM.

Bridges the batched ceremony engine to the real wire protocol: the
reference hybrid-encrypts each (share, hiding) pair per recipient inside
the dealing loop (reference: committee.rs:163-186 → elgamal.rs:134-145).
Here the KEM scalar-mults for *all* (dealer, recipient) pairs run as two
batched device kernels:

    c1[d, i]  = g·r[d, i]          (fixed-base table)
    kem[d, i] = pk_i · r[d, i]     (batched variable-base)

and the byte-level DEM tail (point compression -> Blake2b KDF ->
ChaCha20) is array-shaped too (:func:`seal_shares_batch`): one batched
affine-encode per ceremony (``groups.device.encode_batch``), one
``(N, 16)``-u64 Blake2b compression batch (``crypto.blake2``) and one
``(2·N, 16)``-u32 ChaCha20 state batch (``crypto.chacha``) replace the
per-pair Python loop.  :func:`seal_shares` survives as the scalar
reference leg — ``DKG_TPU_DEM=scalar|batch`` selects, and both legs
produce bit-identical wire bytes (tests/test_dem_batch.py).
:func:`seal_shares_pipeline` chunks deal->KEM->DEM so the host DEM of
chunk k overlaps the device dispatch of chunk k+1
(docs/perf.md "Dealing pipeline").
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..crypto.elgamal import (
    PERSON_RAND,
    PERSON_SHARE,
    HybridCiphertext,
    keystream_from_kem_bytes,
)
from ..fields import host as fh
from ..groups import device as gd
from ..groups import host as gh
from .broadcast import BroadcastPhase1, EncryptedShares


def _chacha():
    try:
        from .. import native

        if native.available():
            return native.chacha20_xor
    except Exception:  # pragma: no cover
        pass
    from ..crypto.chacha import chacha20_xor

    return chacha20_xor


def kem_batch(cfg, pks_dev: jnp.ndarray, r_limbs: jnp.ndarray, g_table: jnp.ndarray):
    """Device KEM for all pairs.

    pks_dev  (n_recipients, C, L) — recipient communication public keys
    r_limbs  (..., n_recipients, L) — fresh encryption randomness
    returns (c1, kem), each (..., n_recipients, C, L).
    """
    cs = cfg.cs
    c1 = gd.fixed_base_mul(cs, g_table, r_limbs)
    kem = gd.scalar_mul(cs, r_limbs, jnp.broadcast_to(pks_dev, r_limbs.shape[:-1] + pks_dev.shape[-2:]))
    return c1, kem


def seal_shares(
    group: gh.HostGroup,
    cfg,
    shares: np.ndarray,  # (n_dealers, n_recipients, L) scalar limbs
    hidings: np.ndarray,
    c1: np.ndarray,  # (n_dealers, n_recipients, C, L) from kem_batch
    kem: np.ndarray,
) -> list[list[tuple[HybridCiphertext, HybridCiphertext]]]:
    """Host DEM: compress KEM points, KDF, stream-cipher the scalars.

    The same KEM point seals both ciphertexts of a pair with distinct
    KDF personalisation, matching one ElGamal exponentiation per
    recipient on the device side.
    """
    xor = _chacha()
    cs = cfg.cs
    fs = cs.scalar
    n_d, n_r = shares.shape[:2]
    out = []
    for d in range(n_d):
        c1_pts = gd.to_host(cs, c1[d])
        kem_pts = gd.to_host(cs, kem[d])
        row = []
        for i in range(n_r):
            kem_bytes = group.encode(kem_pts[i])
            e1 = c1_pts[i]
            cts = []
            for tag, limbs in ((PERSON_SHARE, shares[d, i]), (PERSON_RAND, hidings[d, i])):
                key, nonce = keystream_from_kem_bytes(kem_bytes, tag)
                msg = int(fh.decode_int(fs, limbs)).to_bytes(fs.nbytes, "little")
                cts.append(HybridCiphertext(e1, xor(key, nonce, msg)))
            row.append((cts[0], cts[1]))
        out.append(row)
    return out


def dem_mode() -> str:
    """Which DEM leg seals dealing rounds: ``DKG_TPU_DEM=scalar|batch``
    (validated), default ``batch``.  ``scalar`` is the per-pair
    reference leg the batched path is byte-equivalence-tested against."""
    from ..utils import envknobs

    return (
        envknobs.choice(
            "DKG_TPU_DEM",
            ("scalar", "batch"),
            "DEM sealing path; 'scalar' is the per-pair reference leg",
        )
        or "batch"
    )


def _le_bytes(arr: np.ndarray, nbytes: int) -> np.ndarray:
    """16-bit limb rows ``(N, L)`` -> little-endian byte rows
    ``(N, nbytes)`` (the scalar wire encoding), fully vectorized."""
    le = np.ascontiguousarray(arr.astype("<u2")).view(np.uint8)
    return le[:, :nbytes]


def _host_points(cs, pts: np.ndarray) -> list:
    """Point limb batch ``(N, C, L)`` -> host point tuples (same ints as
    ``gd.to_host``), via one vectorized limbs->bytes pass instead of the
    per-limb Python loop."""
    le = np.ascontiguousarray(pts.astype("<u2")).view(np.uint8)
    return [
        tuple(
            int.from_bytes(le[i, c].tobytes(), "little")
            for c in range(cs.ncoords)
        )
        for i in range(pts.shape[0])
    ]


def seal_shares_batch(
    group: gh.HostGroup,
    cfg,
    shares: np.ndarray,  # (n_dealers, n_recipients, L) scalar limbs
    hidings: np.ndarray,
    c1: np.ndarray,  # (n_dealers, n_recipients, C, L) from kem_batch
    kem: np.ndarray,
) -> list[list[tuple[HybridCiphertext, HybridCiphertext]]]:
    """Array-shaped :func:`seal_shares`: same sealed pairs, bit-identical
    ciphertext and e1 wire bytes, computed by batch entry points —
    ``gd.encode_batch`` (one Montgomery-trick inversion + one transfer
    for every KEM point), ``crypto.blake2.kdf_batch`` (one u64 Blake2b
    compression batch per tag) and ``crypto.chacha.chacha20_xor_batch``
    (every sealed scalar fits one keystream block, so the whole round is
    a single (2·n², 16)-u32 state batch).

    The returned ``e1`` tuples are the same projective tuples the scalar
    leg emits (``gd.to_host`` of the KEM kernel output) — only the KEM
    points need canonicalisation (their *encoding* keys the KDF), so the
    e1 leg skips the inversion entirely.
    """
    from ..crypto.blake2 import kdf_batch
    from ..crypto.chacha import chacha20_xor_batch

    cs = cfg.cs
    fs = cs.scalar
    n_d, n_r = shares.shape[:2]
    n_pairs = n_d * n_r
    shape = (n_pairs, cs.ncoords, cs.field.limbs)
    kem_enc = gd.encode_batch(cs, kem).reshape(n_pairs, -1)
    e1s = _host_points(cs, np.asarray(c1).reshape(shape))
    msg_s = _le_bytes(shares.reshape(n_pairs, -1), fs.nbytes)
    msg_h = _le_bytes(hidings.reshape(n_pairs, -1), fs.nbytes)
    k1, nonce1 = kdf_batch(kem_enc, PERSON_SHARE)
    k2, nonce2 = kdf_batch(kem_enc, PERSON_RAND)
    ct_s = chacha20_xor_batch(k1, nonce1, msg_s)
    ct_h = chacha20_xor_batch(k2, nonce2, msg_h)
    out = []
    for d in range(n_d):
        row = []
        for i in range(n_r):
            j = d * n_r + i
            row.append(
                (
                    HybridCiphertext(e1s[j], ct_s[j].tobytes()),
                    HybridCiphertext(e1s[j], ct_h[j].tobytes()),
                )
            )
        out.append(row)
    return out


def seal_shares_pipeline(
    group: gh.HostGroup,
    cfg,
    shares,  # (n_dealers, n_recipients, L) limbs, device or host
    hidings,
    pks_dev: jnp.ndarray,
    r_enc: jnp.ndarray,  # (n_dealers, n_recipients, L) encryption randomness
    g_table: jnp.ndarray,
    chunk: int | None = None,
) -> list[list[tuple[HybridCiphertext, HybridCiphertext]]]:
    """KEM + DEM for a whole dealing round, chunked over dealers so the
    host DEM of chunk k overlaps the device dispatch of chunk k+1 (JAX
    dispatch is asynchronous; the DEM's single transfer per chunk is
    what blocks, and only on its own chunk's kernels).

    ``chunk`` pins dealers per chunk (0 disables chunking); the default
    targets ~4096 pairs per chunk.  The DEM leg follows
    ``DKG_TPU_DEM`` (:func:`dem_mode`).  Output is bit-identical to an
    unchunked ``kem_batch`` + seal: chunks are independent dealer rows.
    """
    n_d, n_r = r_enc.shape[0], r_enc.shape[1]
    if chunk is None:
        chunk = max(1, 4096 // max(1, n_r))
    seal = seal_shares if dem_mode() == "scalar" else seal_shares_batch
    shares = np.asarray(shares)
    hidings = np.asarray(hidings)
    if not chunk or chunk >= n_d:
        c1, kem = kem_batch(cfg, pks_dev, r_enc, g_table)
        return seal(group, cfg, shares, hidings, np.asarray(c1), np.asarray(kem))
    spans = [(a, min(a + chunk, n_d)) for a in range(0, n_d, chunk)]
    nxt = kem_batch(cfg, pks_dev, r_enc[spans[0][0] : spans[0][1]], g_table)
    out: list[list[tuple[HybridCiphertext, HybridCiphertext]]] = []
    for k, (a, b) in enumerate(spans):
        cur = nxt
        # dispatch chunk k+1 BEFORE blocking on chunk k's transfer
        nxt = (
            kem_batch(
                cfg, pks_dev, r_enc[spans[k + 1][0] : spans[k + 1][1]], g_table
            )
            if k + 1 < len(spans)
            else None
        )
        out.extend(
            seal(
                group, cfg, shares[a:b], hidings[a:b],
                np.asarray(cur[0]), np.asarray(cur[1]),
            )
        )
    return out


def _mesh_slabs(x, spans):
    """Per-shard views of a (possibly mesh-sharded) dealer-major array.

    When ``x`` is a jax array actually sharded over the dealer axis the
    slabs are its resident per-device blocks (``addressable_shards``,
    ordered by global offset) — fetching one never materialises the
    whole array on the host.  Host arrays and replicated/single-device
    layouts fall back to plain slices, so the pipeline below works
    unchanged in unsharded tests.
    """
    import jax as _jax

    per = list(getattr(x, "addressable_shards", ()) or ())
    if isinstance(x, _jax.Array) and len(per) == len(spans):
        per.sort(key=lambda sh: sh.index[0].start or 0)
        starts = [sh.index[0].start or 0 for sh in per]
        if starts == [a for a, _b in spans]:
            return [sh.data for sh in per]
    return [x[a:b] for a, b in spans]


def seal_shares_mesh(
    group: gh.HostGroup,
    cfg,
    mesh,
    shares,  # (n_dealers, n_recipients, L) limbs, mesh-sharded or host
    hidings,
    pks_dev: jnp.ndarray,
    r_enc,  # (n_dealers, n_recipients, L) encryption randomness (host)
    g_table: jnp.ndarray,
    chunk: int | None = None,
) -> list[list[tuple[HybridCiphertext, HybridCiphertext]]]:
    """:func:`seal_shares_pipeline`'s chunk overlap lifted to mesh
    shards: the dealer axis is walked shard block by shard block, so

    * the host only ever materialises ONE shard's (n/ndev, n, L) share
      slab at a time — peak host bytes are O(n^2/ndev), not O(n^2),
      which is what keeps the n=16384 dealing round inside a host
      (scripts/memproof_stream.py records the bound);
    * shard k+1's device->host transfer (``copy_to_host_async``) runs
      under shard k's host DEM, and within a shard the per-chunk
      KEM-dispatch-ahead pipeline runs unchanged.

    Shard blocks are independent dealer rows, so output is bit-identical
    to one ``seal_shares_pipeline`` over the whole round (pinned by
    tests/test_hybrid_batch.py).
    """
    n_dev = int(mesh.devices.size)
    n_d = r_enc.shape[0]
    if n_d % n_dev != 0:
        raise ValueError("dealer count must divide evenly over the mesh")
    block = n_d // n_dev
    spans = [(k * block, (k + 1) * block) for k in range(n_dev)]
    slabs_s = _mesh_slabs(shares, spans)
    slabs_h = _mesh_slabs(hidings, spans)
    for t in (slabs_s[0], slabs_h[0]):
        if hasattr(t, "copy_to_host_async"):
            t.copy_to_host_async()
    out: list[list[tuple[HybridCiphertext, HybridCiphertext]]] = []
    for k, (a, b) in enumerate(spans):
        if k + 1 < n_dev:
            # start shard k+1's transfer BEFORE shard k's DEM blocks
            for t in (slabs_s[k + 1], slabs_h[k + 1]):
                if hasattr(t, "copy_to_host_async"):
                    t.copy_to_host_async()
        out.extend(
            seal_shares_pipeline(
                group, cfg,
                np.asarray(slabs_s[k]), np.asarray(slabs_h[k]),
                pks_dev, r_enc[a:b], g_table, chunk=chunk,
            )
        )
    return out


def open_share(
    group: gh.HostGroup,
    sk: int,
    pair: tuple[HybridCiphertext, HybridCiphertext],
) -> tuple[int | None, int | None]:
    """Recipient-side decryption of a sealed (share, hiding) pair."""
    xor = _chacha()
    fs = group.scalar_field
    share_ct, hiding_ct = pair
    kem_bytes = group.encode(group.scalar_mul(sk, share_ct.e1))
    out = []
    for tag, ct in ((PERSON_SHARE, share_ct), (PERSON_RAND, hiding_ct)):
        key, nonce = keystream_from_kem_bytes(kem_bytes, tag)
        pt = xor(key, nonce, ct.ciphertext)
        v = int.from_bytes(pt, "little") if len(pt) == fs.nbytes else None
        out.append(v if v is None or v < fs.modulus else None)
    return out[0], out[1]


def open_shares_batch(
    group: gh.HostGroup,
    cfg,
    sk: int,
    pairs: list[tuple[HybridCiphertext, HybridCiphertext]],
) -> list[tuple[int | None, int | None]]:
    """Recipient-side :func:`open_share` for all dealers' pairs at once:
    the KEM recoveries ``sk·e1`` run as ONE batched device scalar-mult,
    point compression as one ``gd.encode_batch``, and the KDF/ChaCha
    tail as one batch per tag.  Element semantics match
    :func:`open_share` exactly (shared-KEM pair layout: ``share_ct.e1``
    keys both tags; wrong-length or out-of-range payloads -> None).
    """
    from ..crypto.blake2 import kdf_batch
    from ..crypto.chacha import chacha20_xor_batch

    cs = cfg.cs
    fs = group.scalar_field
    n = len(pairs)
    if n == 0:
        return []
    sk_limbs = jnp.asarray(fh.encode(fs, [sk] * n))
    kem_dev = gd.scalar_mul(
        cs, sk_limbs, gd.from_host(cs, [p[0].e1 for p in pairs])
    )
    kem_enc = gd.encode_batch(cs, np.asarray(kem_dev))
    vals: list[list[int | None]] = [[None, None] for _ in range(n)]
    for col, tag in ((0, PERSON_SHARE), (1, PERSON_RAND)):
        cts = [p[col].ciphertext for p in pairs]
        rows = [i for i, ct in enumerate(cts) if len(ct) == fs.nbytes]
        if not rows:
            continue
        data = np.frombuffer(
            b"".join(cts[i] for i in rows), dtype=np.uint8
        ).reshape(len(rows), fs.nbytes)
        key, nonce = kdf_batch(kem_enc[rows], tag)
        pt = chacha20_xor_batch(key, nonce, data)
        for r, i in enumerate(rows):
            v = int.from_bytes(pt[r].tobytes(), "little")
            vals[i][col] = v if v < fs.modulus else None
    return [(a, b) for a, b in vals]


def broadcasts_from_batch(
    group: gh.HostGroup,
    cfg,
    randomized: np.ndarray,  # (n_dealers, t+1, C, L)
    sealed: list[list[tuple[HybridCiphertext, HybridCiphertext]]],
) -> list[BroadcastPhase1]:
    """Package device-dealt commitments + sealed shares as wire-format
    BroadcastPhase1 messages, one per dealer."""
    cs = cfg.cs
    out = []
    for d, row in enumerate(sealed):
        coeffs = tuple(gd.to_host(cs, randomized[d]))
        enc = tuple(
            EncryptedShares(i + 1, share_ct, hiding_ct)
            for i, (share_ct, hiding_ct) in enumerate(row)
        )
        out.append(BroadcastPhase1(coeffs, enc))
    return out
