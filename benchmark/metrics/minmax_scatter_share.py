"""minmax_scatter_share: of the blocks whose device program folded at least one `min` or `max`, the share that did so by
scatter (`device_routes.fold_minmax_scatter_blocks`: `segment_min` / `segment_max`, scope `fold/segment_minmax`): 100 while
that is the one route. A program that gains another route counts it under a key of the same form,
`fold_minmax_<route>_blocks`, and this reader takes every such key for the whole, so the share falls with no edit here.
None where the window folded no min or max, and where the program has no such counter."""

PREFIX, SUFFIX, SCATTER = "fold_minmax_", "_blocks", "fold_minmax_scatter_blocks"


def read(run: dict):
    scatter = every = 0
    for r in run["responses"]:
        routes = (r.get("stats") or {}).get("device_routes") or {}
        if not isinstance(routes.get(SCATTER), (int, float)):
            continue
        scatter += routes[SCATTER]
        every += sum(v for k, v in routes.items() if k.startswith(PREFIX) and k.endswith(SUFFIX) and isinstance(v, (int, float)))
    return 100.0 * scatter / every if every > 0 else None
