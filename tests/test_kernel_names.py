"""Every ``pallas_call`` site names its kernel: the ``name=`` is what a
profiler trace calls the Mosaic custom call (``%flash_fwd.12``), so it has
to be there, stable, and one site's alone.  Read through the kernel
auditor's interception of the registered audit cases (the kernel bodies
never run)."""

import glob
import os

import pytest

from unicore_tpu.analysis import pallas_audit as pa
from unicore_tpu.analysis.core import ModuleInfo
from unicore_tpu.ops import _pallas

OPS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "unicore_tpu", "ops")
#: the direct sites, as ``tests/test_pallas_audit.py`` pins their count
SITES = {
    "flash_attention.py": 4, "attention_fullrow.py": 2, "fused_norm.py": 3,
    "quant_matmul.py": 1, "softmax_dropout_pallas.py": 1,
    "decode_attention.py": 1,
}


@pytest.fixture(scope="module")
def names_by_site():
    """{(file, line of the call): {names captured there}} over every audit
    case of the tree."""
    modules = [ModuleInfo(p, open(p).read())
               for p in sorted(glob.glob(os.path.join(OPS, "*.py")))]
    kernel_paths = {
        os.path.realpath(m.path) for m in modules if pa.direct_sites(m)
    }
    captures, errors = pa.run_audit_cases(kernel_paths)
    assert not errors, errors
    out = {}
    for c in captures:
        out.setdefault((os.path.basename(c.path), c.line), set()).add(c.name)
    return out


@pytest.mark.parametrize("module", sorted(SITES))
def test_every_site_of_the_module_is_named(names_by_site, module):
    sites = {k: v for k, v in names_by_site.items() if k[0] == module}
    assert len(sites) == SITES[module], sites
    for site, names in sites.items():
        assert all(names), (site, names)


def test_no_two_sites_share_a_name(names_by_site):
    owner = {}
    for site, names in names_by_site.items():
        for name in names:
            assert owner.setdefault(name, site) == site, (name, site)
    # the names the benchmark's readers and flops/kernels.py go by
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_dbias",
            "fullrow_attn_fwd", "fullrow_attn_bwd"} <= set(owner)


def test_a_kernel_without_a_name_is_refused():
    with pytest.raises(TypeError):
        _pallas.pallas_call(lambda *refs: None, out_shape=None)
    with pytest.raises(ValueError):
        _pallas.pallas_call(lambda *refs: None, name="", out_shape=None)
