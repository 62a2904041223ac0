"""Every ``pallas_call`` site compiled for a TPU that is not there.

libtpu describes a v5e 2x2 host without owning a chip, and Mosaic
compiles for it (lightgbm_tpu/testing/tpu_aot.py). A change that makes a
kernel unacceptable to Mosaic — VMEM, tiling, an unaligned slice — fails
here, in the sandbox, instead of costing a chip call. Slow tier: tier-1
compiles no Pallas kernel. Nothing executes, so nothing here is a speed;
the full-shape pre-flight is ``python -m lightgbm_tpu.testing.tpu_aot``.
"""

import pytest

from lightgbm_tpu.testing import tpu_aot

pytestmark = pytest.mark.slow

SHAPE = dict(rows=4096, features=28, bmax=255, slots=16)
SITES = sorted(tpu_aot.kernel_sites(**SHAPE))


@pytest.fixture(scope="module")
def device():
    try:
        return tpu_aot.topology_devices()[0]
    except Exception as exc:   # no libtpu, or one that cannot describe
        pytest.skip("libtpu cannot describe %s here: %s"
                    % (tpu_aot.TOPOLOGY, exc))


def test_topology_is_one_v5e_host(device):
    assert device.platform == "tpu"
    assert len(tpu_aot.topology_devices()) == 4


@pytest.mark.parametrize("quantized", [True, False],
                         ids=["quantized", "exact"])
@pytest.mark.parametrize("site", SITES)
def test_pallas_site_compiles_for_v5e(device, site, quantized):
    fn, args = tpu_aot.kernel_sites(quantized=quantized, **SHAPE)[site]
    compiled, _ = tpu_aot.compile_for(device, fn, *args)
    assert compiled.memory_analysis() is not None
