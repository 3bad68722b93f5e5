"""Per-layer KV pages in the stacked pool layout the serve stack stores.

Tests draw a page pool the way they read it, one layer of (n_pages,
page_size, KVH, hd) codes with (n_pages, page_size, KVH, 1) per-row
scales; ``stacked`` lays it out as ``models.dense.init_paged_cache``
does, one layer deep: (1, n_pages, page_size, KVH·hd) codes and (1,
n_pages, KVH, scale_lanes(page_size)) scales, row r of a page on lane r
and the lanes past the page zero."""
import jax.numpy as jnp

from repro.kernels.paged_attention import scale_lanes


def stacked_codes(codes):
    """(n_pages, G, KVH, hd) -> (1, n_pages, G, KVH·hd)."""
    n_pages, page, kvh, hd = codes.shape
    return jnp.asarray(codes).reshape(1, n_pages, page, kvh * hd)


def stacked_scales(scales):
    """(n_pages, G, KVH, 1) -> (1, n_pages, KVH, scale_lanes(G))."""
    page = scales.shape[1]
    rows = jnp.swapaxes(jnp.asarray(scales)[..., 0], 1, 2)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, scale_lanes(page) - page)))[None]


def stacked(k, ks, v, vs):
    """A one-layer pool (k codes, k scales, v codes, v scales) stacked."""
    return (stacked_codes(k), stacked_scales(ks), stacked_codes(v),
            stacked_scales(vs))
