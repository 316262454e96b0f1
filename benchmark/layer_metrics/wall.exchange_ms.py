"""Mean per query of the milliseconds of its wall in the page exchange
(`queryWall.exchange`: `taskSerialize`, the exchange client's pull rounds
and page decodes, the ICI dispatch) while no thread of the query waited
on the device or ran the pipeline."""
from wall_stats import state_ms


def read(run):
    return state_ms(run, "exchange")
