"""The tpu-batched runtime: SoA actor slabs stepped on device.

See BASELINE.json north star and SURVEY.md §7 step 2. Public surface:

    from akka_tpu.batched import BatchedSystem, behavior, Emit, Inbox, Ctx

    @behavior("counter", {"count": ((), jnp.int32)})
    def counter(state, inbox, ctx):
        return {"count": state["count"] + inbox.count}, Emit.none(1, 4)

    sys = BatchedSystem(capacity=1_000_000, behaviors=[counter])
    ids = sys.spawn_block(counter, 1_000_000)
    sys.tell(0, [1.0]); sys.run(100)
"""

from .autoscale import (AutoscaleDecision, AutoscalePolicy,  # noqa: F401
                        MeshAutoscaler, autoscaler_from_config)
from .behavior import (BatchedBehavior, Ctx, Emit, Folded,  # noqa: F401
                       Inbox, Mailbox, StashBuffer, behavior)
from .bridge import (BatchedRuntimeHandle, DefaultCodec,  # noqa: F401
                     DeviceActorRef, DeviceBlockRef, MessageCodec,
                     device_props, get_handle, reply_dst)
from .core import BatchedSystem  # noqa: F401
from .step import StepCore  # noqa: F401
from .supervision import (ATT_WORDS, COUNTER_NAMES,  # noqa: F401
                          LaneSupervisor, SUP_COLUMNS, decode_attention)
