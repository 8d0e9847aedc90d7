"""ordered: the program is handed the GEO-ordered edges alone, as an
``IncrementalOrderer`` at the configuration's partition count and orderer
options."""
from repro.stream import IncrementalOrderer
from repro.stream.incremental import StreamConfig


def orderer(cell, ordered):
    return IncrementalOrderer(ordered[:, 0], ordered[:, 1], cell.v, regions=cell.k,
                              config=StreamConfig(**cell.config["orderer"]))
