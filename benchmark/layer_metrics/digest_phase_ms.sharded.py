"""parallel/mesh.py programs: milliseconds of a request's transcript digest on the mesh,
`mesh_collective_seconds{op="transcript_digest"}`: the dispatch of `jit_mesh_digest_rows`
(every shard canonicalises and tree-hashes its own 1024 dealers' round-1 tensors in dealer
chunks, `_digest_chunk_default`), the program, the fetch of its 96 bytes a dealer and the
fold into rho, the mean over the requests the window served (`bench_collectives.phase_ms`).
The closed cells' `digest_time_share.bls` reads `jit_affine_canon` and
`jit__tree_from_words_jit`, modules the mesh's one program does not carry, and at the
window's end the traced slice cuts the fifth request's digest or misses it.  None on a
program without the series."""

from bench_collectives import phase_ms


def read(ctx: dict) -> float | None:
    return phase_ms(ctx["counters"], "transcript_digest")
