from repro.kernels.paged_attention.paged_attention import (  # noqa: F401
    collect_heads,
    page_write,
    paged_attention_decode,
    paged_attention_prefill,
    spread_heads,
    touched_pages,
)
from repro.kernels.paged_attention.ref import (  # noqa: F401
    gather_pages,
    paged_attention_ref,
    write_rows,
)
