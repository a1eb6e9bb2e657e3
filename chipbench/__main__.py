import sys

from chipbench.runner import main

sys.exit(main())
