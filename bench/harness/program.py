"""The program under test, configured from a configuration file.

The only place the harness turns a configuration's numbers into the
program's own objects; everything else the drivers take from the program
is its entry points (``HilbertIndex``, ``MutableHilbertIndex``,
``RetrievalEngine``) and its counters.
"""

from __future__ import annotations

from typing import Any, Dict


def index_config(cfg: Dict[str, Any]):
    from repro.core.types import ForestConfig, QuantizerConfig
    from repro.index import IndexConfig

    return IndexConfig(forest=ForestConfig(**cfg["forest"]),
                       quantizer=QuantizerConfig(**cfg["quantizer"]))


def search_params(cfg: Dict[str, Any]):
    from repro.core.types import SearchParams

    return SearchParams(**cfg["search"])


def graph_params(cfg: Dict[str, Any], **override):
    from repro.core.types import GraphParams

    return GraphParams(**{**cfg["graph"], **override})
