"""The contract pages stay in step with the code: every IO stream class is
documented, and every contract page exists and is linked from the README."""
from pathlib import Path

import pytest

from repro.core.iostack import StreamClass

ROOT = Path(__file__).resolve().parents[1]
#: contract pages that must exist and be linked from the README
PAGES = ("docs/architecture.md", "docs/streams.md")


@pytest.mark.parametrize("member", list(StreamClass), ids=lambda m: m.name)
def test_stream_class_documented(member):
    assert member.name in (ROOT / "docs/streams.md").read_text()


@pytest.mark.parametrize("page", PAGES)
def test_contract_page_exists_and_linked_from_readme(page):
    assert (ROOT / page).read_text().strip()
    assert page in (ROOT / "README.md").read_text()
