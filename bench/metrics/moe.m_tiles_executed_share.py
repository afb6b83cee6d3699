"""Share of the MoE grouped GEMM's m-tiles that the ragged kernel
executes, from the live routing of the window's prefills and decodes:
window delta of ``engine_moe_m_tiles_total{kind=executed}`` over
``{kind=total}`` (``models/moe.py`` routing sink, ``serving/engine.py``).
"""


def read(ctx):
    tiles = ctx.counters.get("engine_moe_m_tiles_total", {})
    total = tiles.get('kind="total"', 0)
    if not total:
        return None
    return 100.0 * tiles.get('kind="executed"', 0) / total
