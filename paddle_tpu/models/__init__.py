"""Model zoo — the reference's book/test model families built on the fluid
front end (reference: python/paddle/fluid/tests/book/ +
test_imperative_{resnet,se_resnext,transformer,ptb_rnn}.py)."""
from . import bert  # noqa: F401
from . import laguna  # noqa: F401
from . import phi4_flash  # noqa: F401
from . import qwen3_next  # noqa: F401
from . import resnet  # noqa: F401
from . import smallthinker  # noqa: F401
from . import transformer  # noqa: F401
from . import word2vec  # noqa: F401
from . import ptb_lm  # noqa: F401
from . import se_resnext  # noqa: F401
from . import mnist  # noqa: F401
from . import wide_deep  # noqa: F401
from . import book_extra  # noqa: F401
