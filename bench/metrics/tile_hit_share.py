"""tile_hit_share: tiles answered from the tile cache, as a share of all
tiles the window's viewports asked for (``TileResponse`` counts)."""


def read(run):
    total = run.hits + run.misses
    if run.system != "tile_server" or total == 0:
        return None
    return 100.0 * run.hits / total
