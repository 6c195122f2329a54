"""Geographic data: city positions (Mercator-projected), population-weighted
city sampling.

Reference semantics: core geoinfo/Geo.java, GeoAWS.java, GeoAllCities.java,
CityInfo.java.  Data comes from the baked arrays in wittgenstein_tpu/data
(produced by tools/bake_data.py from the public wondernetwork/city CSVs) or,
if absent, parsed directly from a cities.csv file.
"""

from __future__ import annotations

import csv
import dataclasses
import math
import os
from typing import Dict, Tuple

import numpy as np

MAX_X = 2000
MAX_Y = 1112
MAX_DIST = int(math.sqrt((MAX_X / 2.0) ** 2 + (MAX_Y / 2.0) ** 2))
DEFAULT_CITY = "world"

_DATA_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data")
_REFERENCE_RESOURCES = "/root/reference/core/src/main/resources"


@dataclasses.dataclass(frozen=True)
class CityInfo:
    merc_x: int
    merc_y: int
    cumulative_probability: float


class Geo:
    def cities_position(self) -> Dict[str, CityInfo]:
        raise NotImplementedError

    @staticmethod
    def city_info_map(
        cities: Dict[str, Tuple[int, int, int]], total_population: int
    ) -> Dict[str, CityInfo]:
        """cities: name -> (mercX, mercY, population).  Cumulative probability
        accumulates in iteration order (reference Geo.java:11-19; there the
        order is HashMap order — here it is the dict insertion order, which is
        deterministic; city sampling parity is distributional, not bitwise)."""
        cum = 0.0
        out: Dict[str, CityInfo] = {}
        for name, (x, y, pop) in cities.items():
            cum += pop * 1.0 / total_population
            out[name] = CityInfo(x, y, cum)
        return out


class GeoAWS(Geo):
    """Positions of the 11 AWS-region cities (reference GeoAWS.java:10-23)."""

    CITY_POS: Dict[str, Tuple[int, int, int]] = {
        "Oregon": (271, 261, 1),
        "Virginia": (513, 316, 1),
        "Mumbai": (1344, 426, 1),
        "Seoul": (1641, 312, 1),
        "Singapore": (1507, 532, 1),
        "Sydney": (1773, 777, 1),
        "Tokyo": (1708, 316, 1),
        "Canada central": (422, 256, 1),
        "Frankfurt": (985, 226, 1),
        "Ireland": (891, 200, 1),
        "London": (937, 205, 1),
    }

    def cities_position(self) -> Dict[str, CityInfo]:
        return self.city_info_map(self.CITY_POS, len(self.CITY_POS))


def mercator_x(longitude: float) -> int:
    """Reference GeoAllCities.convertToMercatorX (GeoAllCities.java:60-68)."""
    pos_x = int((longitude + 180) * (MAX_X / 360))
    if pos_x < MAX_X / 2:
        pos_x -= 45
    else:
        pos_x -= 70
    return pos_x


def mercator_y(latitude: float) -> int:
    """Reference GeoAllCities.convertToMercatorY (GeoAllCities.java:70-77)."""
    pos_y = int(math.floor((MAX_Y / 2) - (latitude * MAX_Y / 180) + 0.5))
    if pos_y < 0.2 * MAX_Y:
        pos_y -= 35
    return pos_y


class GeoAllCities(Geo):
    """All ~240 cities from cities.csv with population-weighted probability.

    Loads the baked npz when present, falling back to parsing a cities.csv
    (reference resource format: city,Lat,Long,Population; spaces in names
    become '+'; population gets +200000 — GeoAllCities.java:41-55)."""

    def __init__(self, csv_path: str | None = None):
        baked = os.path.join(_DATA_DIR, "geo_cities.npz")
        if csv_path is None and os.path.exists(baked):
            z = np.load(baked, allow_pickle=False)
            names = [str(s) for s in z["names"]]
            xs, ys, pops = z["merc_x"], z["merc_y"], z["population"]
            cities = {
                n: (int(x), int(y), int(p)) for n, x, y, p in zip(names, xs, ys, pops)
            }
        else:
            if csv_path is None:
                csv_path = os.path.join(_REFERENCE_RESOURCES, "cities.csv")
            cities = parse_cities_csv(csv_path)
        total = sum(v[2] for v in cities.values())
        self._positions = self.city_info_map(cities, total)

    def cities_position(self) -> Dict[str, CityInfo]:
        return dict(self._positions)


def parse_cities_csv(path: str) -> Dict[str, Tuple[int, int, int]]:
    cities: Dict[str, Tuple[int, int, int]] = {}
    with open(path, newline="", encoding="utf-8") as f:
        reader = csv.reader(f)
        next(reader)  # header
        for row in reader:
            if not row:
                continue
            name = row[0].replace(" ", "+")
            lat, lon = float(row[1]), float(row[2])
            population = int(row[3]) + 200000
            cities[name] = (mercator_x(lon), mercator_y(lat), population)
    return cities
