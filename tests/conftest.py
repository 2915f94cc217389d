import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))  # proptest helper


@pytest.fixture(scope="session")
def mesh11():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


@pytest.fixture()
def gsys():
    from repro.core.genesys import Genesys, GenesysConfig
    g = Genesys(GenesysConfig(n_workers=2, coalesce_window_us=100,
                              coalesce_max=8))
    yield g
    g.shutdown()
